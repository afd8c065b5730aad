"""A configuration, a traffic mix and a per-layer metric are added by new
files and entries alone: the harness finds them by name."""
import hashlib
import json
from pathlib import Path

from chipbench import testkit

HOME = Path(__file__).resolve().parent

METRIC = '''"""Batches the ingest pipeline pushed in the window (a counter)."""


def read(window):
    return window.counters.get("fluxsieve_ingest_batches_total") or None
'''


def _digests(home: Path) -> dict:
    return {str(p.relative_to(home)): hashlib.sha256(p.read_bytes()).digest()
            for p in home.rglob("*") if p.is_file()
            and "_work" not in p.parts and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_by_files_only(tmp_path):
    root = testkit.make_root(tmp_path)
    home = root / "benchmarks" / "chip"
    (home / "metrics" / "throwaway_batches.py").write_text(METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "throwaway_batches", "unit": "batches", "better": "higher",
        "source": "program_counter", "layer": "ingest pipeline",
        "moves": "ingest_rps", "workloads": ["tiny.tiny-ingest"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    res = testkit.run(root, "tiny.tiny-ingest", seed=3, trace=1)
    assert res["correct"] is True
    assert res["metrics"]["throwaway_batches"]["value"] > 0
    assert res["metrics"]["throwaway_batches"]["unit"] == "batches"

    # every file the benchmark already had is unchanged
    before, after = _digests(HOME), _digests(home)
    for name, digest in after.items():
        if name in before:
            assert before[name] == digest, name
    added = set(after) - set(before)
    assert added == {"configs/tiny.json", "traffic/tiny-query.json",
                     "traffic/tiny-ingest.json",
                     "metrics/throwaway_batches.py"}
