"""The readers of the program's request- and seal-level spans: each on a
hand-made window with a known answer, the device's idle time inside the
query spans by intersecting intervals, and each read from a traced run
at a tiny size on the CPU."""
import functools
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import testkit, tracing
from chipbench.harness import Window, load_module

HOME = Path(__file__).resolve().parent

MEANS = {   # metric -> the span it averages
    "serve_queue_ms": "serve/queue_wait",
    "serve_respond_ms": "serve/respond",
    "query_plan_ms": "query/plan",
    "query_arrangement_ms": "query/arrangement",
    "query_device_wait_ms": "query/device_wait",
    "query_materialize_ms": "query/materialize",
    "store_seal_ms": "store/seal",
    "store_spill_ms": "store/spill",
    "ingest_wal_truncate_ms": "ingest/wal_truncate",
}


def reader(metric):
    return load_module(HOME / "metrics" / f"{metric}.py",
                       "chipbench_metric_" + metric)


def window(spans, t0=0.0, t1=10.0, device=None):
    """What the readers see of ``harness.Window``: spans in Chrome form
    on a tracer whose epoch is perf_counter 0, and the window's edges."""
    w = SimpleNamespace(spans=spans, t0=t0, t1=t1, epoch=0.0, device=device)
    w.spans_named = functools.partial(Window.spans_named, w)
    return w


def span(name, ts_s, dur_ms, **args):
    return {"name": name, "ts": ts_s * 1e6, "dur": dur_ms * 1e3,
            "args": {"id": 0, **args}}


@pytest.mark.parametrize("metric", sorted(MEANS))
def test_mean_of_its_spans(metric):
    name = MEANS[metric]
    args = {"outcome": "admitted"} if name == "serve/queue_wait" else {}
    spans = [span(name, 1.0, 2.0, **args), span(name, 2.0, 4.0, **args),
             span(name, 3.0, 9.0, **args),
             span(name, 11.0, 50.0, **args),    # after the window
             span("serve/request", 4.0, 70.0)]  # another span
    if name == "serve/queue_wait":              # shed, not admitted
        spans.append(span(name, 5.0, 30.0, outcome="queue_full"))
    assert reader(metric).read(window(spans)) == pytest.approx(5.0)
    assert reader(metric).read(window([span("serve/request", 1.0, 2.0)])) \
        is None


# one chip; profiler clock = perf_counter + 1 s.  Busy [0, 0.5] and
# [1.4, 2.0] s: the gap [0.5, 1.4] has its midpoint, 0.95 s, before the
# query span [1.0, 1.6] opens, yet 0.4 s of it lie inside the span
DEVICE = {"offset_ns": 1e9, "chips": [{
    "plane": "/device:TPU:0", "modules": [],
    "ops": [["fusion", 1.0e9, 0.5e9], ["fusion", 2.4e9, 0.6e9]]}]}


def test_device_idle_inside_query_spans_by_intersection():
    dev = tracing.Reduced(DEVICE, 0.0, 2.0)
    spans = [span("query/execute", 1.0, 600.0, id=1),
             span("query/execute", 0.1, 200.0, id=2),   # all busy
             span("query/plan", 1.0, 100.0, id=3)]
    # the midpoint rule files the straddling gap under no span: 0 here
    gaps = dict(dev.idle_gaps(spans, 0.0))
    assert "query/execute" not in gaps
    assert gaps["no_host_span"] == pytest.approx(0.9)
    got = reader("query_device_idle_ms").read(window(spans, 0.0, 2.0, dev))
    assert got == pytest.approx((400.0 + 0.0) / 2)


def test_device_idle_counts_a_gap_inside_and_the_window_edge():
    dev = tracing.Reduced(DEVICE, 0.0, 2.0)
    # [0.3, 1.9]: idle [0.5, 1.4] whole, busy elsewhere; one span past
    # the window's end counts up to the end only
    spans = [span("query/execute", 0.3, 1600.0),
             span("query/execute", 1.95, 500.0)]
    got = reader("query_device_idle_ms").read(window(spans, 0.0, 2.0, dev))
    assert got == pytest.approx((900.0 + 0.0) / 2)
    assert reader("query_device_idle_ms").read(
        window(spans, 0.0, 2.0, None)) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testkit.make_root(tmp_path_factory.mktemp("chipbench-spans"))


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.tiny-query", {"serve_queue_ms", "serve_respond_ms",
                         "query_plan_ms", "query_arrangement_ms",
                         "query_device_wait_ms", "query_materialize_ms"}),
    ("tiny.tiny-ingest", {"store_seal_ms", "store_spill_ms",
                          "ingest_wal_truncate_ms"}),
])
def test_traced_run_reads_the_span_metrics(root, cell, metrics):
    res = testkit.run(root, cell, seed=2 ** 31 + 7, trace=1)
    assert res["correct"] is True
    assert metrics <= set(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in metrics)
    assert all(res["metrics"][m]["unit"] == "ms" for m in metrics)
