"""The benchmark's entry and result line, on the CPU at a tiny size."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import testkit

HOME = Path(__file__).resolve().parent
REPO = HOME.parents[1]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testkit.make_root(tmp_path_factory.mktemp("chipbench"))


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.tiny-ingest", {"ingest_rps", "setup_s"}),
    ("tiny.tiny-query", {"query_p50_ms", "query_p95_ms", "setup_s"}),
])
def test_result_line_keys(root, cell, metrics):
    res = testkit.run(root, cell, seed=2 ** 31 + 11)
    assert list(res) == KEYS
    assert res["correct"] is True
    assert set(res["metrics"]) == metrics
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    json.dumps(res)


def test_traced_result_line_keys(root):
    res = testkit.run(root, "tiny.tiny-query", seed=5, trace=1)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"]
    assert {"serve_request_ms", "query_execute_ms",
            "loadgen_late_p95_ms"} <= set(res["metrics"])
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_query_store_prepared_once(root):
    """A query cell's store is made once per seed and reopened after."""
    from chipbench.harness import Run
    run = Run(root, "tiny.tiny-query", 2 ** 31 + 23, 1, False, False)
    assert not run.driver.prepared(run)
    res = testkit.run(root, "tiny.tiny-query", seed=2 ** 31 + 23)
    assert res["correct"] is True
    assert run.driver.prepared(run)


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "query-dense-2f", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_no_tpu_fails_without_result():
    p = _entry(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_benchmark_directory_fails(tmp_path):
    """A directory with only BENCHMARK.json and the files under paths
    (no program) exits non-zero and prints no result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(HOME, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("_work", "__pycache__"))
    p = _entry(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
