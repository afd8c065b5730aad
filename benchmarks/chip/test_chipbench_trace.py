"""The reduction from the profiler's trace to device metrics: on a hand
made record with known answers, and on a trace recorded on a TPU v5e
(``testdata/``), where each number is recounted here another way."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import tracing

DATA = Path(__file__).resolve().parent / "testdata"

# one chip; times in ns on the profiler's clock, perf_counter 0 at 1e9 ns
HAND = {"offset_ns": 1e9, "chips": [{
    "plane": "/device:TPU:0",
    "modules": [["jit_a(1)", 1.0e9, 0.4e9], ["jit_b(2)", 1.5e9, 0.3e9]],
    "ops": [["fusion", 1.0e9, 0.25e9], ["copy", 1.2e9, 0.2e9],
            ["fusion", 1.5e9, 0.3e9]]}]}
SPANS = [  # Chrome events of the program's tracer, epoch at perf_counter 0
    {"name": "outer", "ts": 0.0, "dur": 1e6},
    {"name": "inner", "ts": 0.45e6, "dur": 0.1e6}]


def test_hand_made_record():
    r = tracing.Reduced(HAND, 0.0, 1.0)
    # busy: [1.0, 1.4] and [1.5, 1.8] seconds
    assert r.busy_s == pytest.approx(0.7)
    assert r.window_s == 1.0
    assert r.module_s("jit_a") == pytest.approx(0.4)
    assert r.module_s("jit_") == pytest.approx(0.7)
    assert dict(r.top_ops()) == pytest.approx(
        {"jit_a/fusion": 0.25, "jit_a/copy": 0.2, "jit_b/fusion": 0.3})
    # idle [1.4, 1.5] inside "inner" (its midpoint 1.45), [1.8, 2.0]
    # inside "outer" only
    assert dict(r.idle_gaps(SPANS, 0.0)) == pytest.approx(
        {"inner": 0.1, "outer": 0.2})


def test_window_clips_intervals():
    r = tracing.Reduced(HAND, 0.1, 0.6)
    assert r.busy_s == pytest.approx(0.3 + 0.1)
    assert r.module_s("jit_b") == pytest.approx(0.1)


def _recount_busy(ops, lo, hi):
    """Busy seconds by a sweep over interval edges."""
    edges = []
    for _, s, d in ops:
        a, b = max(s / 1e9, lo), min((s + d) / 1e9, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


@pytest.mark.parametrize("name", sorted(p.name for p in
                                        DATA.glob("*.json.gz")))
def test_chip_trace(name):
    with gzip.open(DATA / name, "rt") as f:
        saved = json.load(f)
    rec, t0, t1 = saved["record"], saved["t0"], saved["t1"]
    r = tracing.Reduced(rec, t0, t1)
    lo, hi = t0 + rec["offset_ns"] / 1e9, t1 + rec["offset_ns"] / 1e9
    ops = rec["chips"][0]["ops"]
    assert r.busy_s == pytest.approx(_recount_busy(ops, lo, hi), rel=1e-9)
    assert 0 < r.busy_s <= r.window_s
    assert r.busy_s == pytest.approx(saved["busy_s"], rel=1e-9)
    mod = saved["module"]
    want = sum(min((s + d) / 1e9, hi) - max(s / 1e9, lo)
               for n, s, d in rec["chips"][0]["modules"]
               if n.startswith(mod) and min((s + d) / 1e9, hi)
               > max(s / 1e9, lo))
    assert r.module_s(mod) == pytest.approx(want, rel=1e-9)
    assert r.module_s(mod) == pytest.approx(saved["module_s"], rel=1e-9)
    top = r.top_ops()
    assert top == sorted(top, key=lambda kv: -kv[1]) and len(top) <= 10
    gaps = r.idle_gaps(saved["spans"], saved["epoch"], k=1000)
    assert sum(v for _, v in gaps) == pytest.approx(r.window_s - r.busy_s,
                                                    rel=1e-6)
    assert np.isfinite([v for _, v in top + gaps]).all()
