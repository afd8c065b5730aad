"""A small copy of the benchmark for tests on the CPU: the benchmark's
files under a temporary root, tiny cells added by files and entries, and
the program's sources linked in."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HOME = Path(__file__).resolve().parents[1]
REPO = HOME.parents[1]

TINY_CONFIG = {
    "source": "tiny test deployment", "content_fields": 2, "text_width": 512,
    "vocab_seed": 7, "ultra_rate": 2e-3, "high_rate": 1e-2, "rules": 40,
    "dense_rule": {"name": "dense_er", "term": "er", "fields": ["content1"]},
    "batch_size": 512, "segment_size": 1024, "store_records": 4096,
    "wal": True, "mode": "enrich", "match_backend": "dfa_ref",
    "text_index_fields": [],
    "frontend": {"max_inflight": 8, "max_queue": 32, "deadline_s": 5.0,
                 "rate_per_client": 100.0, "burst": 100.0}}

TINY_QUERY = {
    "kind": "query", "rate_per_s": 20, "clients": 4, "connections": 4,
    "client_timeout_s": 30, "modes": {"count": 0.8, "ids": 0.2},
    "queries": [
        {"terms": [["content1", "er"], ["content1", "HIGHneedle1x"]],
         "weight": 1},
        {"terms": [["content2", "HIGHneedle2x"]], "weight": 1}]}

TINY_INGEST = {"kind": "ingest", "pool_records": 2048,
               "warmup_records": 1024}

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def make_root(tmp: Path) -> Path:
    """A checkout-like root under ``tmp`` with the benchmark's files, a
    tiny configuration, two tiny mixes and their cells."""
    root = Path(tmp)
    home = root / "benchmarks" / "chip"
    shutil.copytree(HOME, home, ignore=shutil.ignore_patterns(
        "_work", "testdata", "test_*.py", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    add(root, bench, "tiny", TINY_CONFIG,
        {"tiny-query": TINY_QUERY, "tiny-ingest": TINY_INGEST})
    return root


def add(root: Path, bench: dict, config: str, cfg: dict,
        mixes: dict) -> None:
    """Add a configuration and one cell per mix, by new files and
    entries only."""
    home = root / "benchmarks" / "chip"
    (home / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": config, "source": cfg["source"],
                             "file": f"benchmarks/chip/configs/{config}.json",
                             "reduced": []})
    for mix, traffic in mixes.items():
        (home / "traffic" / f"{mix}.json").write_text(json.dumps(traffic))
        cell = f"{config}.{mix}"
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": mix, "chips": 1,
                                   "why": "test"})
        kind = "ingest_rps" if traffic["kind"] == "ingest" else None
        for m in bench["end_to_end"] + bench["per_layer"]:
            ws = m.get("workloads")
            if ws is None:
                continue
            like = [w for w in ws if w.startswith(
                "ingest" if kind else "query")]
            if like:
                ws.append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))


def run(root: Path, cell: str, seed: int = 1, seconds: int = 1,
        trace: int = 0, control: int = 0) -> dict:
    """One run of ``cell`` on the CPU, past the harness's look for a
    chip; returns the result line."""
    import time
    from chipbench import harness
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--control", str(control)]
    return harness.run_cell(argv, root=root, t_start=time.monotonic(),
                            device=dict(CPU))
