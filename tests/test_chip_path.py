"""What stands between the main path and a chip: lanes that cannot compile
refuse when built, process pools refuse a device one process owns, and
``chip_smoke.py`` fails without a TPU.  Each test steers
``jax.default_backend`` itself; no program option exists for it."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core.matcher import FusedMatcher, MatchEngine, compile_bundle
from repro.core.patterns import Rule, RuleSet
from repro.core.stream_processor import StreamProcessor

REPO = Path(__file__).resolve().parent.parent
RULES = RuleSet((Rule(0, "err", "ERROR", fields=("*",)),))


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def bundle():
    return compile_bundle(RULES, ("content1",))


@pytest.mark.parametrize("backend", ["dfa", "shift_or"])
def test_uncompilable_lane_refuses_when_built(on_tpu, bundle, backend):
    engine = bundle.engines["content1"]
    with pytest.raises(NotImplementedError, match="dfa_ref"):
        MatchEngine(engine, backend=backend, ruleset=RULES)
    with pytest.raises(NotImplementedError, match="Mosaic|lower"):
        StreamProcessor(bundle, backend=backend)
    if backend == "dfa":
        with pytest.raises(NotImplementedError):
            FusedMatcher(bundle, backend=backend)


def test_compiled_lanes_still_build_on_tpu(on_tpu, bundle):
    """dfa_ref (the default) and the CPU-only lanes on the CPU build."""
    StreamProcessor(bundle, backend="dfa_ref")
    MatchEngine(bundle.engines["content1"], backend="dfa_selective")


def test_uncompilable_lane_builds_on_cpu(bundle):
    assert jax.default_backend() == "cpu"
    MatchEngine(bundle.engines["content1"], backend="dfa")
    FusedMatcher(bundle, backend="dfa")


def test_process_pools_refuse_a_device(on_tpu, tmp_path):
    from repro.core.maintenance import ProcessMaintenancePool
    from repro.core.query.process_shards import ProcessQueryPool
    with pytest.raises(RuntimeError, match="MaintenanceWorkerPool"):
        ProcessMaintenancePool(tmp_path, objects_root=tmp_path / "objects")
    with pytest.raises(RuntimeError, match="thread shards"):
        ProcessQueryPool(tmp_path, RULES)


def test_ingest_cli_refuses_process_workers_on_a_device(on_tpu, tmp_path,
                                                        capsys):
    from repro.launch.ingest import main
    with pytest.raises(SystemExit) as e:
        main(["--records", "100", "--store", str(tmp_path),
              "--worker-model", "process", "--maintenance"])
    assert e.value.code == 2
    assert "--worker-model thread" in capsys.readouterr().err


def test_chip_smoke_fails_without_a_tpu():
    """On the CPU every phase passes at a tiny size, and the run still
    fails: the device check is the only failure, and the last line says
    ok false."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--records", "6000"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode != 0, proc.stdout
    last = json.loads(lines[-1])
    assert last == {"ok": False, "device": {"platform": "cpu", "kind": "cpu",
                                            "count": 1}}
    fails = [ln for ln in lines if ln.startswith("FAIL ")]
    assert len(fails) == 1 and "no TPU" in fails[0], proc.stdout
    for q in ("Q1", "Q2", "Q3", "Q4"):
        assert any(ln.startswith(f"query {q} ") for ln in lines)
        assert any(ln.startswith(f"serve {q} ids status=200") for ln in lines)


def test_column_major_bitmaps_seal_like_row_major():
    """A TPU can hand a narrow (N, W) bitmap back in column-major order;
    every host consumer that views its bytes must not care."""
    import numpy as np
    from repro.core import enrichment
    from repro.core.query.store import derive_enrichment_meta
    rng = np.random.default_rng(0)
    bm = rng.integers(0, 2 ** 32, size=(64, 4), dtype=np.uint32)
    bm[rng.random(64) < 0.9] = 0
    f_order = np.asfortranarray(bm)[10:50]      # a sealed slice of a batch
    assert not f_order.flags.c_contiguous
    meta_c, post_c = derive_enrichment_meta(bm[10:50])
    meta_f, post_f = derive_enrichment_meta(f_order)
    assert meta_f == meta_c
    assert post_f.keys() == post_c.keys()
    assert all(np.array_equal(post_f[k], post_c[k]) for k in post_c)
    np.testing.assert_array_equal(enrichment.popcount(f_order),
                                  enrichment.popcount(bm[10:50]))
    np.testing.assert_array_equal(enrichment.to_bool_columns(f_order, 128),
                                  enrichment.to_bool_columns(bm[10:50], 128))
