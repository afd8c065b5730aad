"""Run every benchmark (one per paper table/figure) at CI-friendly sizes.

    PYTHONPATH=src python -m benchmarks.run [--quick|--smoke] [--only NAME]
                                            [--json OUT.json]

CSV schema: name,median_us,[ci_lo..ci_hi]us,n=runs,derived...

``--json`` additionally writes machine-readable results (name, median_s,
derived metrics, git sha) so per-PR perf deltas are trajectory-trackable
instead of anecdotal — commit them as ``BENCH_<name>.json``.  ``--smoke``
runs tiny sizes (seconds total) so CI can catch kernel-path regressions.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
import traceback


def _git_sha() -> str:
    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip()
        dirty = subprocess.run(["git", "status", "--porcelain"],
                               capture_output=True, text=True,
                               timeout=10).stdout.strip()
        return f"{sha}-dirty" if dirty else sha
    except Exception:  # noqa: BLE001 — sha is best-effort metadata
        return ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes (seconds per bench)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke (implies --quick scale)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="OUT.json",
                    help="also write machine-readable results")
    ap.add_argument("--telemetry-dump", default=None, metavar="DIR",
                    help="write metrics.prom / snapshot.json / trace.json "
                         "for the whole run into DIR (CI artifact)")
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_backfill, bench_layout_grid, bench_matcher,
                            bench_overhead, bench_query_concurrency,
                            bench_scale, bench_serve, bench_speedup,
                            bench_standing, bench_storage, bench_update)
    from benchmarks.common import print_rows

    if args.smoke:
        overhead_n, matcher_b, storage_n = 5_000, 256, 5_000
    elif args.quick:
        overhead_n, matcher_b, storage_n = 20_000, 512, 20_000
    else:
        overhead_n, matcher_b, storage_n = 60_000, 2048, 80_000

    def entry(fn, **params):
        """Suite entry carrying its RESOLVED parameters, so --json output
        is self-describing (worker/shard/client counts, sizes) instead of
        requiring the reader to re-derive them from argv + defaults."""
        return ((lambda: fn(**params)), params)

    suite = {
        "overhead": entry(bench_overhead.run, num_records=overhead_n),
        "matcher": entry(bench_matcher.run, batch=matcher_b),
        "update": entry(bench_update.run),
        "storage": entry(bench_storage.run, num_records=storage_n),
        "layout_grid": entry(
            bench_layout_grid.run,
            num_records=40_000 if args.quick else 100_000,
            runs=3 if args.quick else 5),
        "scale": entry(
            bench_scale.run,
            sizes=(40_000, 80_000) if args.quick else (125_000, 250_000),
            runs_hot=3 if args.quick else 5,
            runs_cold=2 if args.quick else 3),
        "speedup_ultra": entry(
            bench_speedup.run, selectivity="ultra",
            num_records=40_000 if args.quick else 150_000,
            runs=3 if args.quick else 5),
        "speedup_high": entry(
            bench_speedup.run, selectivity="high",
            num_records=40_000 if args.quick else 150_000,
            runs=3 if args.quick else 5),
        "backfill": entry(
            bench_backfill.run,
            num_records=(6_000 if args.smoke
                         else 20_000 if args.quick else 60_000),
            segment_size=(600 if args.smoke
                          else 2_000 if args.quick else 5_000),
            runs=2 if args.smoke else 3 if args.quick else 5,
            workers=(1, 2),
            # process lanes run even in smoke (one spawn-pool backfill
            # lane) so the durable-control-plane path regresses loudly
            process_workers=(1, 2),
            scale_records=12_000 if args.smoke or args.quick else 24_000,
            scale_segment=1_500,
            scale_repeats=2 if args.smoke else 3 if args.quick else 5),
        "standing": entry(
            bench_standing.run,
            tiers=((6, 12) if args.smoke
                   else (10, 30, 60) if args.quick else (20, 80, 200)),
            segment_size=400 if args.smoke else 500 if args.quick else 600,
            runs=3 if args.smoke else 5 if args.quick else 7,
            churn_epochs=4 if args.smoke else 6 if args.quick else 10),
        "serve": entry(
            bench_serve.run,
            num_records=(4_000 if args.smoke
                         else 20_000 if args.quick else 60_000),
            segment_size=(800 if args.smoke
                          else 4_000 if args.quick else 10_000),
            num_rules=50 if args.smoke else 150 if args.quick else 300,
            clients=4 if args.smoke else 6 if args.quick else 8,
            requests_per_client=(8 if args.smoke
                                 else 25 if args.quick else 50),
            overload_clients=(8 if args.smoke
                              else 12 if args.quick else 16),
            overload_seconds=(1.5 if args.smoke
                              else 2.0 if args.quick else 3.0),
            cardinality_clients=(1_500 if args.smoke
                                 else 20_000 if args.quick else 100_000)),
        "query": entry(
            bench_query_concurrency.run,
            num_records=(4_000 if args.smoke
                         else 40_000 if args.quick else 120_000),
            segment_size=(800 if args.smoke
                          else 5_000 if args.quick else 10_000),
            clients=4 if args.smoke else 8 if args.quick else 12,
            rounds=2 if args.smoke else 4 if args.quick else 6,
            runs_hot=3 if args.smoke else 5 if args.quick else 7,
            process_shards=2),
    }
    if args.only and args.only not in suite:
        print(f"unknown bench {args.only!r} (available: {', '.join(suite)})",
              file=sys.stderr)
        return 1
    if args.smoke:
        # CI smoke: the kernel-path benches must run to completion so
        # enrich, query, AND distributed-maintenance regressions fail the
        # build, not only the nightly eyeball
        smoke_names = ("overhead", "matcher", "query", "backfill",
                       "standing", "serve")
        if args.only and args.only not in smoke_names:
            print(f"bench {args.only!r} is excluded by --smoke "
                  f"(smoke runs: {', '.join(smoke_names)})", file=sys.stderr)
            return 1
        suite = {k: suite[k] for k in smoke_names}
    from repro.core import telemetry

    failures = 0
    results = {}
    ran_params = {}
    suite_telemetry = {}
    for name, (fn, params) in suite.items():
        if args.only and name != args.only:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        # per-suite telemetry isolation: metrics zero in place (cached
        # handles stay valid), so each suite's snapshot carries ITS
        # counters — provenance alongside timings in BENCH_*.json
        telemetry.metrics.reset()
        telemetry.events.reset()
        try:
            rows = fn()
            print_rows(rows)
            results[name] = [m.to_dict() for m in rows]
            ran_params[name] = {k: list(v) if isinstance(v, tuple) else v
                                for k, v in params.items()}
            suite_telemetry[name] = telemetry.metrics.snapshot()
        except Exception:
            failures += 1
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if args.json:
        doc = {"git_sha": _git_sha(),
               "argv": [a for a in (argv or sys.argv[1:])],
               "config": {
                   "scale": ("smoke" if args.smoke
                             else "quick" if args.quick else "full"),
                   "suites": ran_params,
               },
               "benches": results,
               "telemetry": suite_telemetry}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)
    if args.telemetry_dump:
        paths = telemetry.write_dump(args.telemetry_dump)
        print(f"# telemetry dump: {', '.join(sorted(paths.values()))}",
              flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
