"""Query execution over the columnar plane — planner/executor split.

Three logical paths (paper §5.1/§6.1 baselines + the paper's fast path):

  full_scan   vectorized substring scan over raw content bytes
              (the DuckDB optimized-full-scan baseline, paper §5.1);
  text_index  token -> posting-list lookup on the per-segment inverted
              index (the Pinot FTS baseline, paper §6.1);
  fluxsieve   enrichment-bitmap evaluation + segment zone-map pruning
              (the paper's fast path, via the Query Mapper).

A query is a conjunction of (field contains term) predicates with a
``copy`` (materialize matching records) or ``count`` (aggregate only) mode —
exactly the paper's Q1-Q4 and their "with count" variants.  ``cold=True``
drops all segment caches (host AND device) first, modelling the paper's
cold runs; bytes read from disk are accounted per query.

Execution is split into a logical **planner** (``query.planner``) that
consults the mapper/zone-maps/metadata once and classifies every segment
into a physical path class, and a batched **executor** (``query.executor``)
that runs all bitmap-scan segments as ONE stacked device dispatch with one
D2H transfer per query, leases hot device state from the SHARED
refcounted arrangement plane (``query.arrangement`` — one upload per word
column per maintenance epoch across ALL concurrent queries and shards),
and re-plans segments the maintenance plane swapped mid-query.
Consistency (paper §3.4 step 4) is preserved: records ingested under an
engine version that did not know a rule fall back to full scan for that
segment (hybrid execution), so enrichment never changes results.

The plane's invariants, each asserted in tests:

  * results are byte-identical across ``full_scan`` / ``text_index`` /
    ``fluxsieve`` and across every fluxsieve path class — before, during,
    and after any maintenance action;
  * ONE counted D2H transfer per query on the stacked bitmap path
    (``executor.transfer_count``, under ``jax.transfer_guard``), ONE fused
    matcher D2H for all fallback/full-scan segments of a query;
  * ONE H2D upload per enrichment word column per maintenance epoch,
    shared by all concurrent clients and shards
    (``ArrangementStore.upload_counts`` — every value == 1);
  * enriched-path results re-validate the meta snapshot their
    classification used (meta-flips-last on the writer side makes the
    check sufficient); swapped segments re-plan individually, full scans
    return directly because they never read enrichment state.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from repro.core import telemetry
from repro.core.records import RecordBatch
from repro.core.query.arrangement import ArrangementStore
from repro.core.query.executor import (PlanExecutor, ShardedQueryExecutor,
                                       substring_scan)  # noqa: F401 — substring_scan re-exported
from repro.core.query.planner import PhysicalPlan, QueryPlanner
from repro.core.query.store import Segment, SegmentStore  # noqa: F401

PATHS = ("full_scan", "text_index", "fluxsieve")

# per-path latency histograms: the paper's Fig-6/7 axis in snapshot form
_QUERY_LATENCY = {
    p: telemetry.histogram("fluxsieve_query_latency_seconds",
                           labels={"path": p},
                           help="End-to-end query latency by logical path.")
    for p in PATHS
}
_QUERY_TOTAL = telemetry.counter(
    "fluxsieve_query_total", help="Queries executed.")
_QUERY_BYTES = telemetry.counter(
    "fluxsieve_query_bytes_read_total",
    help="Bytes read from spill by queries (cold-path I/O).")
_QUERY_PARTIAL = telemetry.counter(
    "fluxsieve_query_partial_total",
    help="Queries answered partially (>=1 shard failed or timed out).")


def filter_expired(task, ids: np.ndarray, cache: bool) -> tuple:
    """Retention-straddler filter: expired rows are plan-time invisible
    long before compaction physically drops them.  ONE central filter —
    every physical class (and the standing-query fold path) funnels its
    row ids through here, so no per-path filter can tear.  Returns
    ``(kept_ids, bytes_read)`` (bytes only when the timestamp column came
    off disk)."""
    if task.cutoff is None or not len(ids):
        return ids, 0
    seg = task.seg
    in_mem = "timestamp" in seg._columns
    ts = np.asarray(seg.column_rows("timestamp", ids, cache=cache))
    return ids[ts >= task.cutoff], (0 if in_mem else ts.nbytes)


@dataclass(frozen=True)
class Query:
    """terms: ((field, term), ...) AND-combined; mode: 'copy' | 'count'."""
    terms: tuple
    mode: str = "count"
    name: str = ""

    def __post_init__(self):
        if self.mode not in ("copy", "count"):
            raise ValueError(self.mode)
        if not self.terms:
            raise ValueError("empty query")

    def key(self) -> tuple:
        return tuple(sorted(self.terms))


@dataclass
class QueryResult:
    count: int
    records: RecordBatch = None
    latency_s: float = 0.0
    path: str = ""
    segments_scanned: int = 0
    segments_pruned: int = 0
    segments_fallback: int = 0
    segments_failed: int = 0    # shard faulted/deadline overrun: unserved
    segments_total: int = 0
    bytes_read: int = 0
    fallback_ids: tuple = ()    # segment ids served via consistency fallback
    failed_segment_ids: tuple = ()  # segment ids a degraded query skipped
    path_classes: dict = field(default_factory=dict)  # class -> num segments

    @property
    def partial(self) -> bool:
        """True when >=1 segment went unserved: ``count``/``records`` are a
        lower bound over ``coverage`` of the store, not the full answer."""
        return self.segments_failed > 0

    @property
    def coverage(self) -> float:
        """Fraction of planned segments actually served (1.0 = complete)."""
        if not self.segments_total:
            return 1.0
        return 1.0 - self.segments_failed / self.segments_total


class QueryEngine:
    """``backend`` selects the bitmap-class executor: ``"ref"`` (stacked jnp
    dispatch, default), ``"pallas"`` (stacked Pallas kernel), ``"numpy"``
    (pre-refactor per-segment word tests — the equivalence oracle).
    ``scan_backend`` (e.g. ``"dfa_ref"``) routes full-scan fallbacks through
    throwaway compiled DFA engines (fused backends batch ALL scan segments
    of a query into one dispatch).  ``workers`` > 1 scans host-path
    segments concurrently (numpy releases the GIL in the vectorized
    kernels) — the intra-query parallelism axis of the paper's Figs 6-9.

    Device state is the SHARED arrangement plane: pass one
    ``arrangements=ArrangementStore()`` to every engine over a store (or
    share one engine) and concurrent queries lease a single refcounted
    device copy per (segment set, word subset) — uploaded once per
    maintenance epoch.  The engine subscribes the arrangement store to the
    segment store's maintenance feed, so ``apply_update`` / compaction /
    cold-run drops publish epochs instead of invalidating under readers.
    ``shards`` > 1 turns on the sharded query workers: ``plan.tasks``
    partition by segment across a pool (identities
    ``{worker_id}/shard-{i}``), each shard dispatching and re-planning
    independently against the shared arrangements."""

    def __init__(self, store: SegmentStore, *, mapper=None, profiler=None,
                 workers: int = 1, backend: str = "ref",
                 scan_backend: str = None, block_n: int = 1024,
                 arrangements: ArrangementStore = None,
                 device_counts="auto", shards: int = 1,
                 worker_id: str = "query-0", shard_deadline_s: float = None,
                 shard_affinity: str = "weighted", prefetch: bool = True):
        self.store = store
        self.mapper = mapper          # QueryMapper (None -> no fluxsieve path)
        self.profiler = profiler
        self.workers = workers
        self.planner = QueryPlanner(mapper)
        self.arrangements = arrangements or ArrangementStore()
        # maintenance swaps publish kind-aware epoch deltas to the shared
        # device plane (on_epoch retires + optionally prefetches; seals
        # pass through without bumping the arrangement epoch)
        store.subscribe_epochs(self.arrangements.on_epoch)
        if prefetch:
            self.arrangements.set_prefetch_source(self._prefetch_item)
        self.plan_executor = PlanExecutor(
            backend=backend, scan_backend=scan_backend, block_n=block_n,
            workers=workers,
            arrangements=self.arrangements, device_counts=device_counts)
        self.executor = (ShardedQueryExecutor(self.plan_executor,
                                              shards=shards,
                                              worker_id=worker_id,
                                              deadline_s=shard_deadline_s,
                                              affinity=shard_affinity)
                         if shards > 1 else self.plan_executor)
        self._standing = None         # StandingRegistry, built on demand

    def close(self) -> None:
        """Release standing queries and the shard worker pool (both no-ops
        when unused)."""
        if self._standing is not None:
            self._standing.close()
        if isinstance(self.executor, ShardedQueryExecutor):
            self.executor.close()

    def _prefetch_item(self, segment_id: int):
        """Arrangement-prefetch source: the segment's CURRENT-token
        ``ArrangementItem`` (hot bitmap read), or None once it left the
        store."""
        from repro.core.stream_processor import ENRICH_COLUMN
        from repro.core.query.arrangement import ArrangementItem
        for seg in self.store.segments:
            if seg.segment_id == segment_id:
                return ArrangementItem(
                    token=seg.meta_token(),
                    num_records=int(seg.num_records),
                    load=lambda s=seg: np.asarray(s.column(ENRICH_COLUMN)))
        return None

    # -- standing queries ----------------------------------------------------
    def register_standing(self, query: Query, *, path: str = "auto",
                          name: str = None):
        """Register ``query`` for incremental view maintenance: the result
        materializes once through the normal executor, then per-segment
        deltas from the store's epoch feed fold into it — ``refresh()``
        answers in O(changed segments) instead of O(all segments).
        Returns the :class:`repro.core.query.standing.StandingQuery`."""
        from repro.core.query.standing import StandingRegistry
        if self._standing is None:
            self._standing = StandingRegistry(self)
            self.store.subscribe_epochs(self._standing.on_epoch)
        return self._standing.register(query, path=path, name=name)

    # -- public ------------------------------------------------------------
    def plan(self, query: Query, *, path: str = "auto",
             cache: bool = True) -> PhysicalPlan:
        """EXPLAIN: the physical plan ``execute`` would run (fresh per call;
        classifications snapshot live segment metadata)."""
        flux = None
        if path in ("auto", "fluxsieve") and self.mapper is not None:
            flux = self.mapper.map(query)
        return self.planner.plan(query, list(self.store.segments),
                                 path=path, flux=flux, cache=cache)

    def execute(self, query: Query, *, path: str = "auto",
                cold: bool = False) -> QueryResult:
        if cold:
            self.store.drop_caches()    # token bump also invalidates device
        flux = None
        if path in ("auto", "fluxsieve") and self.mapper is not None:
            flux = self.mapper.map(query)
        if path == "fluxsieve" and flux is None:
            raise ValueError("query not covered by registered rules; "
                             "no fluxsieve plan")
        t0 = time.perf_counter()
        with telemetry.span("query/execute", cat="query",
                            mode=query.mode, query=query.name):
            with telemetry.span("query/plan", cat="query"):
                plan = self.planner.plan(query, list(self.store.segments),
                                         path=path, flux=flux,
                                         cache=not cold)
            res = self._run(plan, cache=not cold)
        res.latency_s = time.perf_counter() - t0
        res.path = plan.path
        _QUERY_TOTAL.inc()
        _QUERY_BYTES.inc(res.bytes_read)
        hist = _QUERY_LATENCY.get(res.path)
        if hist is not None:
            hist.observe(res.latency_s)
        if self.profiler is not None:
            self.profiler.record(query, res)
        return res

    # -- execution ---------------------------------------------------------
    def _run(self, plan: PhysicalPlan, cache: bool) -> QueryResult:
        res = QueryResult(count=0, segments_total=len(plan.tasks))
        per_seg = self.executor.execute(plan, self.planner, cache=cache)
        matches = []   # (segment, ids) for copy mode
        for task, (ids, stats) in zip(plan.tasks, per_seg):
            res.segments_scanned += stats.scanned
            res.segments_pruned += stats.pruned
            res.segments_fallback += stats.fallback
            res.segments_failed += stats.failed
            res.bytes_read += stats.bytes_read
            res.fallback_ids += stats.fallback_ids
            res.failed_segment_ids += stats.failed_ids
            if stats.path_class:
                res.path_classes[stats.path_class] = \
                    res.path_classes.get(stats.path_class, 0) + 1
            if ids is None:
                continue
            if isinstance(ids, (int, np.integer)):   # metadata-only count
                res.count += int(ids)
                continue
            ids, extra_bytes = filter_expired(task, ids, cache)
            res.bytes_read += extra_bytes
            res.count += len(ids)
            if plan.query.mode == "copy" and len(ids):
                matches.append((task.seg, ids))
        if plan.query.mode == "copy":
            with telemetry.span("query/materialize", cat="query",
                                segments=len(matches)):
                res.records = self._materialize(matches, cache, res)
        if res.segments_failed:
            _QUERY_PARTIAL.inc()
            telemetry.emit("query_partial", plane="query",
                           failed=res.segments_failed,
                           total=res.segments_total,
                           segments=[int(s) for s in res.failed_segment_ids])
        return res

    def _materialize(self, matches, cache, res) -> RecordBatch:
        parts = []
        for seg, ids in matches:
            cols = {}
            for name in seg.column_names:
                in_mem = name in seg._columns
                rows = seg.column_rows(name, ids, cache=cache)
                if not in_mem:
                    res.bytes_read += rows.nbytes
                cols[name] = rows
            parts.append(RecordBatch(cols))
        if not parts:
            return RecordBatch({})
        return RecordBatch.concat(parts)
