"""Standalone FluxSieve ingestion driver — the paper's deployment shape:
source -> stream processor (multi-pattern match + enrich) -> columnar store,
with the updater feedback loop live (profiler promotes hot predicates).

    PYTHONPATH=src python -m repro.launch.ingest --records 100000 \\
        --rules 1000 --mode enrich --store /tmp/segments
"""
from __future__ import annotations

import argparse
import os
import threading
import time
from dataclasses import dataclass
from pathlib import Path

from repro import compile_cache
from repro.core import telemetry
from repro.core.control_plane import (CONTROL_DIRNAME, ControlBus,
                                      DurableControlBus)
from repro.core.maintenance import (Compactor, MaintenancePolicy,
                                    MaintenanceScheduler,
                                    MaintenanceWorkerPool,
                                    ProcessMaintenancePool, RetentionPolicy,
                                    RetentionWorker, SpillGC)
from repro.core.matcher import compile_bundle
from repro.core.object_store import ObjectStore
from repro.core.patterns import Rule, RuleSet
from repro.core.query.engine import Query, QueryEngine
from repro.core.query.mapper import QueryMapper
from repro.core.query.profiler import QueryProfiler
from repro.core.query.store import (INGEST_WAL_DIRNAME, MANIFEST_NAME,
                                    SegmentStore)
from repro.core.stream_processor import StreamProcessor
from repro.core.updater import MatcherUpdater
from repro.data.generator import LogGenerator, WorkloadSpec
from repro.data.pipeline import IngestPipeline
from repro.device import on_cpu


def synth_ruleset(spec: WorkloadSpec, num_rules: int) -> RuleSet:
    """Planted-term rules + filler literal rules (the paper evaluates
    1000-pattern rule sets; filler rules match nothing by construction)."""
    rules = [Rule(i, t.term, t.term, fields=(t.fieldname,))
             for i, t in enumerate(spec.planted)]
    k = len(rules)
    for i in range(k, num_rules):
        rules.append(Rule(i, f"filler{i}", f"QQfiller{i:04d}qq", fields=("*",)))
    return RuleSet(tuple(rules))


@dataclass
class World:
    """What ``build_world`` ingested, and the planes around it."""
    spec: WorkloadSpec
    gen: LogGenerator
    full_ruleset: RuleSet   # every rule, the late one included
    ruleset: RuleSet        # the rules active at ingest
    late_rule: Rule         # held back for the maintenance round, or None
    bus: object
    ostore: ObjectStore
    updater: MatcherUpdater
    proc: StreamProcessor
    store: SegmentStore
    times: object           # data.pipeline.StageTimes
    mapper: QueryMapper
    profiler: QueryProfiler
    engine: QueryEngine


def build_world(spec: WorkloadSpec, *, rules: int = 1000,
                mode: str = "enrich", backend: str = "dfa_ref",
                store=None, wal: bool = False, segment_size: int = 50_000,
                batch_size: int = 4096, hold_back_late_rule: bool = False,
                durable_control: bool = False, ruleset: RuleSet = None,
                index_fields: tuple = (), shards: int = 1) -> World:
    """Generate ``spec``'s log records, match and enrich them through the
    stream processor, and seal them into a segment store (spilled under
    ``store`` when given, with a text index on ``index_fields``).
    ``ruleset`` defaults to ``synth_ruleset``; ``hold_back_late_rule``
    leaves the last planted rule out of the ingest engine so sealed
    segments need a backfill once it activates.  ``durable_control`` puts
    the bus and the artifact store under ``store`` for worker processes.
    ``shards`` is the query engine's thread-shard width."""
    gen = LogGenerator(spec)
    full_ruleset = ruleset or synth_ruleset(spec, rules)
    late_rule = None
    active = full_ruleset
    if hold_back_late_rule:
        late_rule = next(r for r in full_ruleset.rules
                         if r.rule_id == len(spec.planted) - 1)
        active = full_ruleset.without_ids([late_rule.rule_id])
    t0 = time.perf_counter()
    bundle = compile_bundle(active, spec.content_fields)
    print(f"compiled {active.num_rules} rules in "
          f"{time.perf_counter() - t0:.2f}s "
          f"({sum(e.num_states for e in bundle.engines.values())} DFA states)")
    root = Path(store) if store is not None else None
    if durable_control:
        # durable control plane: worker processes open the same files
        bus = DurableControlBus(root / CONTROL_DIRNAME)
        ostore = ObjectStore(root=root / "objects")
    else:
        bus, ostore = ControlBus(), ObjectStore()
    updater = MatcherUpdater(ostore, bus, spec.content_fields,
                             initial=active)
    proc = StreamProcessor(bundle, mode=mode, backend=backend,
                           bus=bus, store=ostore)
    if root is not None and ((root / MANIFEST_NAME).exists()
                             or (root / INGEST_WAL_DIRNAME).exists()):
        # restart over a populated root: reopen the committed store (a
        # fresh SegmentStore here would disown every durable segment on
        # its first manifest commit)
        seg_store = SegmentStore.load(root, segment_size=segment_size,
                                      index_fields=index_fields)
    else:
        seg_store = SegmentStore(segment_size=segment_size, root=store,
                                 index_fields=index_fields)
    pipe = IngestPipeline(gen, seg_store, proc, wal=wal)
    start = pipe.recover() if wal else 0
    times = pipe.run(batch_size=batch_size, start=start)
    mapper = QueryMapper(active)
    profiler = QueryProfiler()
    engine = QueryEngine(seg_store, mapper=mapper, profiler=profiler,
                         shards=shards)
    return World(spec=spec, gen=gen, full_ruleset=full_ruleset,
                 ruleset=active, late_rule=late_rule, bus=bus,
                 ostore=ostore, updater=updater, proc=proc, store=seg_store,
                 times=times, mapper=mapper, profiler=profiler,
                 engine=engine)


def run_maintenance(world: World, *, workers: int = 1,
                    worker_model: str = "thread", backend: str = "dfa_ref",
                    retention: int = None):
    """One maintenance round: activate the held-back rule, backfill the
    sealed segments, compact, and optionally retire old data.  Asserts
    that the late rule's count equals the full scan before, during and
    after.  Returns the worker pool (a process pool must be closed)."""
    spec, store, qe = world.spec, world.store, world.engine
    planted = spec.planted[world.late_rule.rule_id]
    q = Query(terms=((planted.fieldname, planted.term),), mode="count")
    # the invariant is store-level: fluxsieve == full scan over what was
    # ingested.  (In filter mode records matching ONLY the late rule were
    # dropped before it existed — backfill cannot resurrect them, so the
    # generator's ground truth is not the reference.)
    late_truth = qe.execute(q, path="full_scan").count
    if world.proc.mode == "enrich":
        assert late_truth == world.gen.true_count(planted)
    handle = world.updater.submit(world.full_ruleset, asynchronous=False)
    assert handle.published, handle.error
    world.proc.poll_updates()
    world.mapper.notify(world.full_ruleset,
                        version_id=world.proc.active_version_id)
    r_pre = qe.execute(q, path="fluxsieve")
    print(f"maintenance: late rule {world.late_rule.name!r} pre-backfill "
          f"count={r_pre.count} (truth {late_truth}) "
          f"fallback_segments={r_pre.segments_fallback} "
          f"{r_pre.latency_s * 1e3:.2f} ms")
    scheduler = MaintenanceScheduler(
        world.profiler,
        MaintenancePolicy(max_records_per_cycle=store.segment_size))
    if worker_model == "process":
        pool = ProcessMaintenancePool(
            store.root, store=store, objects_root=store.root / "objects",
            num_workers=workers, policy=scheduler.policy, backend=backend)
    else:
        pool = MaintenanceWorkerPool(store, world.bus, world.ostore,
                                     num_workers=workers,
                                     scheduler=scheduler, backend=backend)
    rep = pool.run_until_converged()
    print(f"maintenance: backfilled {rep.segments_backfilled} segments "
          f"({rep.records} records, {rep.bytes_rewritten / 1e6:.1f} MB) "
          f"across {len(pool.worker_ids)} {worker_model} worker(s) "
          f"in {rep.seconds:.2f}s; acked={rep.acked}")
    status = world.updater.await_maintenance(rep.version, pool.worker_ids)
    r_post = qe.execute(q, path="fluxsieve")
    print(f"maintenance: post-backfill count={r_post.count} "
          f"fallback_segments={r_post.segments_fallback} "
          f"{r_post.latency_s * 1e3:.2f} ms "
          f"(rollout complete={status.complete})")
    assert r_post.count == r_pre.count == late_truth
    assert r_post.segments_fallback == 0
    crep = Compactor(store, leases=pool.leases).run_cycle()
    print(f"maintenance: compaction merged {crep.segments_in} -> "
          f"{crep.segments_out} segments "
          f"({len(store.segments)} total now)")
    r_c = qe.execute(q)
    assert r_c.count == late_truth
    if retention is not None:
        before = store.num_records
        ret = RetentionWorker(store, RetentionPolicy(max_age=retention),
                              leases=pool.leases)
        rrep = ret.run_cycle()
        prep = Compactor(store, leases=pool.leases).run_cycle()
        grep_ = SpillGC(store, arrangements=qe.arrangements,
                        grace_s=0.0).run_cycle()
        print(f"retention: horizon={rrep.horizon} expired "
              f"{rrep.segments_expired} segments "
              f"({rrep.records_expired} records), purged "
              f"{prep.rows_purged} straddler rows, GC deleted "
              f"{grep_.dirs_deleted} spill dirs "
              f"({store.num_records}/{before} records retained)")
    return pool


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--records", type=int, default=100_000)
    ap.add_argument("--rules", type=int, default=1000)
    ap.add_argument("--mode", default="enrich", choices=("enrich", "filter"))
    ap.add_argument("--backend", default="dfa_ref",
                    choices=("dfa", "dfa_ref", "shift_or", "parallel"))
    ap.add_argument("--store", default=None, help="spill directory")
    ap.add_argument("--wal", action="store_true",
                    help="crash-safe ingest: journal every raw batch to "
                         "<store>/ingest-wal before dispatch and truncate "
                         "against the manifest watermark (needs --store; "
                         "enrich mode only)")
    ap.add_argument("--segment-size", type=int, default=50_000)
    ap.add_argument("--batch-size", type=int, default=4096)
    ap.add_argument("--fields", type=int, default=2)
    ap.add_argument("--maintenance", action="store_true",
                    help="run the segment maintenance plane after ingest: "
                         "hold back one rule, activate it late, backfill "
                         "sealed segments (plus a compaction pass)")
    ap.add_argument("--maintenance-workers", type=int, default=1,
                    metavar="N",
                    help="distributed maintenance: N leased backfill "
                         "workers sharding segments by id hash, each with "
                         "its own consumer-group offsets and per-shard "
                         "convergence ack")
    ap.add_argument("--worker-model",
                    default=os.environ.get("FLUXSIEVE_WORKER_MODEL",
                                           "thread"),
                    choices=("thread", "process"),
                    help="maintenance worker substrate: 'thread' shares one "
                         "interpreter; 'process' runs each worker as a "
                         "spawn process over the durable control plane "
                         "(file-backed bus + leases under <store>/, needs "
                         "--store; CPU backend only — a chip belongs to "
                         "one process)")
    ap.add_argument("--retention", type=int, default=None, metavar="AGE",
                    help="event-time TTL (timestamp-column units): after "
                         "maintenance, retire segments older than AGE past "
                         "the newest sealed timestamp, purge straddling "
                         "rows via compaction, and GC drained spill dirs")
    ap.add_argument("--metrics-dump", default=None, metavar="DIR",
                    help="write metrics.prom / snapshot.json / trace.json "
                         "into DIR at the end of the run")
    ap.add_argument("--metrics-interval", type=float, default=None,
                    metavar="S",
                    help="with --metrics-dump: additionally rewrite "
                         "metrics.prom every S seconds while running")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.wal and args.store is None:
        ap.error("--wal needs --store (the journal lives next to the "
                 "spill dirs)")
    if args.worker_model == "process":
        if args.store is None:
            ap.error("--worker-model process needs --store (worker "
                     "processes coordinate through the durable bus/leases "
                     "under it)")
        if not on_cpu():
            ap.error("--worker-model process runs on the CPU backend only: "
                     "its worker processes build matchers and would need "
                     "the device this process holds; use --worker-model "
                     "thread")

    stop_dumper = None
    if args.metrics_dump and args.metrics_interval:
        stop_dumper = threading.Event()

        def _periodic():
            while not stop_dumper.wait(args.metrics_interval):
                telemetry.write_dump(args.metrics_dump)

        threading.Thread(target=_periodic, daemon=True,
                         name="metrics-dumper").start()

    world = build_world(WorkloadSpec(num_records=args.records,
                                     num_content_fields=args.fields),
                        rules=args.rules, mode=args.mode,
                        backend=args.backend, store=args.store, wal=args.wal,
                        segment_size=args.segment_size,
                        batch_size=args.batch_size,
                        hold_back_late_rule=args.maintenance,
                        durable_control=args.worker_model == "process")
    times, proc, store = world.times, world.proc, world.store
    print(f"ingested {times.records} records in "
          f"{times.generate_s + times.process_s + times.store_s:.2f}s "
          f"({times.throughput():,.0f} rec/s; "
          f"match+enrich {times.process_s:.2f}s; cpu {times.cpu_s:.2f}s)")
    print(f"segments: {len(store.segments)}, matched "
          f"{proc.stats.records_matched}/{proc.stats.records_in}")

    # query the enriched store through the mapper
    term = world.spec.planted[0]
    res = world.engine.execute(Query(terms=((term.fieldname, term.term),),
                                     mode="count"))
    truth = world.gen.true_count(term)
    print(f"query[{term.term}] path={res.path} count={res.count} "
          f"(truth {truth}) in {res.latency_s * 1e3:.2f} ms")
    assert res.count == truth

    pool = None
    if args.maintenance:
        pool = run_maintenance(world, workers=args.maintenance_workers,
                               worker_model=args.worker_model,
                               backend=args.backend,
                               retention=args.retention)
    if stop_dumper is not None:
        stop_dumper.set()
    if args.metrics_dump:
        if args.worker_model == "process" and pool is not None:
            # each worker process dumps under its own prefix, the parent
            # under "parent.", then everything folds into merged.* —
            # one snapshot covering every process
            pool.write_dumps(args.metrics_dump)
            telemetry.write_dump(args.metrics_dump, prefix="parent.")
            paths = telemetry.merge_dumps(args.metrics_dump)
        else:
            paths = telemetry.write_dump(args.metrics_dump)
        print(f"telemetry: wrote {', '.join(sorted(paths.values()))}")
    if pool is not None and args.worker_model == "process":
        pool.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
