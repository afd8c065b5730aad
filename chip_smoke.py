"""Drive FluxSieve's main path once on one TPU chip and check its answers.

    python chip_smoke.py [--records N]

Phases, all in this one process (a chip belongs to one process):

  ingest       generate ``--records`` log records (2 content fields of 512
               bytes) from the generator's seed, match them against 1000
               rules on the ``dfa_ref`` lane, and seal them into a spilled
               segment store in segments of 50,000, with the ingest WAL on
               and the text index ``launch/serve.py --port`` builds;
  maintenance  activate the rule held back at ingest, backfill the sealed
               segments with thread workers, compact (full segments leave
               nothing to merge);
  query        Q1-Q4 in count and ids mode through ``QueryEngine`` with the
               ``ref`` and ``pallas`` bitmap lanes, each checked against
               the numpy full-scan oracle and the generator's truth;
  serve        the same queries as framed requests to ``FrontEnd`` on
               localhost, each checked against the direct engine call.

The run fails if a phase fails, if any lane that could stand in for the
device was used (fallback batches, dispatch errors, quarantined records,
suppressed errors, partial query results), or if the device is not a TPU.
The last line of output is ``{"ok": ..., "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import compile_cache  # noqa: E402

# counters that move only when something other than the primary device
# lane answered: each must stay 0
GUARD_COUNTERS = (
    "fluxsieve_match_fallback_batches_total",
    "fluxsieve_match_dispatch_errors_total",
    "fluxsieve_ingest_quarantined_total",
    "fluxsieve_errors_suppressed_total",
    "fluxsieve_query_partial_total",
)
# a content1 bigram in ~43% of records: no posting list, so a conjunction
# with it is answered by the bitmap kernels on the device
DENSE_TERM = "er"


class PhaseClock:
    """Seconds JAX spends tracing, lowering and compiling, per phase, and
    the wall-clock second at which each phase ended (the run must fit its
    time limit; neither is a measurement of the system)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.t0 = time.monotonic()
        self.phase = "setup"
        self.compile_s: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.compile_s[self.phase] = (self.compile_s.get(self.phase, 0.0)
                                          + duration)

    def start(self, phase: str) -> str:
        if self.phase != "setup":
            print(f"phase {self.phase} ended at "
                  f"{time.monotonic() - self.t0} s", flush=True)
        self.phase = phase
        return phase


def build_ruleset(spec, num_rules: int):
    """``synth_ruleset``'s planted and filler rules plus one dense rule,
    ``num_rules`` in all."""
    from repro.core.patterns import Rule, RuleSet
    from repro.launch.ingest import synth_ruleset
    base = synth_ruleset(spec, num_rules - 1)
    dense = Rule(num_rules - 1, "dense_er", DENSE_TERM, fields=("content1",))
    return RuleSet(base.rules + (dense,))


def queries(spec) -> dict:
    """Q1 absent term, Q2 ultra-selective term, Q3 the late (backfilled)
    high-selectivity term, Q4 the conjunction of the dense rule and Q3."""
    ultra = next(t for t in spec.planted
                 if t.fieldname == "content1" and t.rate == spec.ultra_rate)
    late = spec.planted[-1]
    return {
        "Q1": ((("content1", spec.absent_terms[0]),), []),
        "Q2": (((ultra.fieldname, ultra.term),), [ultra]),
        "Q3": (((late.fieldname, late.term),), [late]),
        "Q4": ((("content1", DENSE_TERM), (late.fieldname, late.term)),
               [late]),
    }


def record_ids(res):
    """Sorted record indices of a copy-mode result (timestamp = 1000 * i)."""
    import numpy as np
    if res.records is None or not len(res.records):
        return np.zeros(0, np.int64)
    return np.sort(np.asarray(res.records.columns["timestamp"])) // 1000


def phase_query(world, engines, fails) -> dict:
    import numpy as np
    from repro.core.query.engine import Query
    from repro.core.query.planner import BITMAP
    gen, n = world.gen, world.spec.num_records
    out = {}
    for name, (terms, planted) in queries(world.spec).items():
        oracle = world.engine.execute(Query(terms=terms, mode="copy"),
                                      path="full_scan")
        oracle_ids = record_ids(oracle)
        # generator truth: exact for a single planted term, and a superset
        # bound for the conjunction with the dense rule
        truth = np.arange(n)
        for t in planted:
            truth = np.intersect1d(truth, np.flatnonzero(
                gen.plant_mask(t, 0, n)))
        if not planted:
            truth = truth[:0]
        exact = len(terms) == len(planted)
        if exact and not np.array_equal(oracle_ids, truth):
            fails.append(f"{name}: full scan {len(oracle_ids)} ids != "
                         f"generator truth {len(truth)}")
        if not exact and not np.isin(oracle_ids, truth).all():
            fails.append(f"{name}: full scan returned ids outside the "
                         f"generator truth")
        row = {"oracle": int(oracle.count), "truth": int(len(truth))}
        for lane, engine in engines.items():
            rc = engine.execute(Query(terms=terms, mode="count"))
            ri = engine.execute(Query(terms=terms, mode="copy"))
            ids = record_ids(ri)
            row[lane] = {"count": int(rc.count), "ids": int(len(ids)),
                         "classes": dict(ri.path_classes)}
            if rc.partial or ri.partial:
                fails.append(f"{name}/{lane}: partial result")
            if rc.count != oracle.count or not np.array_equal(ids,
                                                              oracle_ids):
                fails.append(f"{name}/{lane}: count {rc.count} ids "
                             f"{len(ids)} != full scan {oracle.count}")
            if name == "Q4" and not ri.path_classes.get(BITMAP):
                fails.append(f"{name}/{lane}: no segment took the device "
                             f"bitmap path ({ri.path_classes})")
        out[name] = row
        print(f"query {name} {json.dumps(row, sort_keys=True)}", flush=True)
    return out


def phase_serve(world, engine, fails) -> None:
    from repro.core.query.engine import Query
    from repro.serve.frontend import FrontEnd, ServeClient, result_payload
    with FrontEnd(engine, port=0, rate_per_client=1000.0,
                  default_deadline_s=600.0) as fe:
        with ServeClient(fe.host, fe.port, client_id="chip-smoke",
                         timeout=600.0) as client:
            for name, (terms, _) in queries(world.spec).items():
                for mode in ("count", "ids"):
                    got = client.query(terms, mode=mode)
                    want = result_payload(engine.execute(Query(
                        terms=terms,
                        mode="count" if mode == "count" else "copy")), mode)
                    keys = ("count", "ids", "partial")
                    if got.get("status") != 200 or any(
                            got.get(k) != want.get(k) for k in keys):
                        fails.append(f"serve {name}/{mode}: got "
                                     f"status={got.get('status')} "
                                     f"count={got.get('count')}, engine "
                                     f"count={want['count']}")
                    print(f"serve {name} {mode} status={got.get('status')} "
                          f"count={got.get('count')}", flush=True)


def guard_counts() -> dict:
    from repro.core.telemetry import metrics
    totals = dict.fromkeys(GUARD_COUNTERS, 0)
    for kind, name, m in metrics.REGISTRY.collect():
        if kind == "counter" and name in totals:
            totals[name] += int(m.value)
    return totals


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.
                                 RawDescriptionHelpFormatter)
    ap.add_argument("--records", type=int, default=1_000_000)
    args = ap.parse_args(argv)
    compile_cache.enable()

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device {json.dumps(device)}", flush=True)

    from repro.core.query.engine import QueryEngine
    from repro.data.generator import WorkloadSpec
    from repro.launch.ingest import build_world, run_maintenance

    clock = PhaseClock()
    fails: list = []
    phase = "setup"
    try:
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
            phase = clock.start("ingest")
            spec = WorkloadSpec(num_records=args.records)
            world = build_world(spec, ruleset=build_ruleset(spec, 1000),
                                store=Path(tmp) / "store", wal=True,
                                segment_size=50_000, batch_size=4096,
                                hold_back_late_rule=True,
                                index_fields=spec.content_fields)
            print(f"ingest: {world.times.records} records, "
                  f"{world.full_ruleset.num_rules} rules "
                  f"({world.ruleset.num_rules} active), "
                  f"{len(world.store.segments)} segments, "
                  f"{world.store.sealed_rows} rows sealed", flush=True)
            if world.store.sealed_rows != args.records:
                fails.append(f"ingest sealed {world.store.sealed_rows} of "
                             f"{args.records} records")

            phase = clock.start("maintenance")
            run_maintenance(world, workers=1, worker_model="thread")

            phase = clock.start("query")
            engines = {"ref": world.engine,
                       "pallas": QueryEngine(world.store,
                                             mapper=world.mapper,
                                             backend="pallas")}
            phase_query(world, engines, fails)

            phase = clock.start("serve")
            phase_serve(world, engines["pallas"], fails)
            for engine in engines.values():
                engine.close()
            phase = clock.start("checks")
    except Exception as e:  # noqa: BLE001 — reported, and the run fails
        traceback.print_exc()
        fails.append(f"phase {phase} raised {type(e).__name__}: {e}")

    guards = guard_counts()
    print(f"guard counters {json.dumps(guards, sort_keys=True)}")
    fails += [f"{name} = {v}" for name, v in guards.items() if v]
    print("compile seconds by phase "
          + json.dumps(clock.compile_s))
    if device["platform"] != "tpu":
        fails.append(f"no TPU: JAX reports platform {device['platform']!r}")
    for f in fails:
        print(f"FAIL {f}")
    ok = not fails
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
