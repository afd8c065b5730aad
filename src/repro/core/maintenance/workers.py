"""MaintenanceWorkerPool — N leased, sharded backfill workers over one store.

The distributed maintenance plane: ``SEGMENT_MAINTENANCE`` consumption is
sharded by segment-id hash (``lease.shard_of``) across ``num_workers``
:class:`~repro.core.maintenance.backfill.BackfillWorker` instances.  Each
worker keeps its OWN consumer-group offsets on the control bus (the
consumer-group plumbing the bus already provides), so delivery stays
at-least-once *per worker*: a crashed worker's replacement re-reads from
its own committed offset and cannot lose a target, and no worker's
progress gates another's.

Exclusion is layered, not assumed:

  * the shard map is the fast path — disjoint shards never contend;
  * a shared :class:`~repro.core.maintenance.lease.LeaseManager` is the
    correctness path — every install runs under a per-segment lease whose
    epoch is the fencing token ``Segment.apply_update`` checks, so even a
    misconfigured (overlapping) pool or a resurrected zombie worker cannot
    interleave writes.  A crashed worker's lease expires; its segments
    become acquirable instead of wedging the shard.

Convergence acks are per worker (one ``MAINTENANCE_ACKS`` message per
worker id once ITS shard is drained); the updater awaits the full
``pool.worker_ids`` set, so "maintenance rollout complete" still means
every sealed segment in the store is at the target.

``run_cycle`` fans the workers out on threads.  The heavy per-segment work
— DFA matching through the jitted XLA backends, numpy bitmap derivation —
releases the GIL, so co-located workers overlap on cores; in a real
deployment each worker is its own process/host and only the bus, store,
and lease manager are shared infrastructure.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

from repro.core import telemetry
from repro.core.maintenance.backfill import (BackfillReport, BackfillWorker,
                                             merge_reports)
from repro.core.maintenance.lease import LeaseManager


class MaintenanceWorkerPool:
    """N sharded, leased backfill workers sharing one store/bus/object
    store.  Mirrors the single worker's ``run_cycle`` /
    ``run_until_converged`` / ``set_target`` surface so callers (and the
    test matrix's ``FLUXSIEVE_MAINT_WORKERS`` leg) swap it in unchanged;
    reports merge across workers (counters sum, ``pending_after`` is the
    store-wide pending count).

    One ``matcher_cache`` is shared by all workers: compiled delta matchers
    are immutable once built, so N workers pay one compile per
    (version, delta, fields) instead of N.  This sharing is a THREAD-model
    property only — the cache holds jitted engines that cannot cross a
    process boundary, so ``ProcessMaintenancePool`` gives each worker
    process a private cache and warms it once per target version
    (``BackfillWorker.warm_matchers``) instead."""

    def __init__(self, store, bus, object_store, *, num_workers: int = 2,
                 scheduler=None, leases: LeaseManager = None,
                 backend: str = "dfa_ref", block_n: int = 256,
                 rows_per_pass: int = None,
                 worker_prefix: str = "maint", lease_ttl: float = 30.0,
                 matcher_cache: dict = None):
        if num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {num_workers}")
        self.store = store
        self.leases = leases if leases is not None else LeaseManager(
            ttl=lease_ttl, manifest=getattr(store, "manifest", None))
        self._matcher_cache: dict = (matcher_cache if matcher_cache
                                     is not None else {})
        self.workers = [
            BackfillWorker(store, bus, object_store,
                           worker_id=f"{worker_prefix}-{i}",
                           scheduler=scheduler, backend=backend,
                           block_n=block_n,
                           shard_index=i, num_shards=num_workers,
                           leases=self.leases, rows_per_pass=rows_per_pass,
                           matcher_cache=self._matcher_cache)
            for i in range(num_workers)]
        # one persistent executor for the pool's lifetime: convergence
        # under tight row budgets runs MANY cycles, and paying thread
        # spawn/join per cycle is overhead on the path this class speeds
        # up (same discipline as ShardedQueryExecutor's shard pool)
        self._pool = (ThreadPoolExecutor(num_workers,
                                         thread_name_prefix=worker_prefix)
                      if num_workers > 1 else None)

    def close(self) -> None:
        """Shut the cycle executor down (idle threads exit); called at
        finalization too, so churning pools does not accumulate
        process-lifetime threads."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)

    def __del__(self):
        self.close()

    @property
    def worker_ids(self) -> tuple:
        """Identities acking on ``MAINTENANCE_ACKS`` — pass to
        ``MatcherUpdater.await_maintenance``."""
        return tuple(w.worker_id for w in self.workers)

    def set_target(self, ruleset) -> None:
        """Direct (bus-less) targeting of every worker."""
        for w in self.workers:
            w.set_target(ruleset)

    def pending_segments(self) -> list:
        """Union of every shard's pending set (store-wide lag)."""
        out = []
        for w in self.workers:
            out.extend(w.pending_segments())
        return out

    def run_cycle(self, *, max_segments: int = None) -> BackfillReport:
        """One pool cycle: every worker polls its offsets and backfills its
        shard, concurrently.  ``max_segments`` bounds each WORKER's pass
        (the per-cycle budget knob stays per-worker, like the scheduler's)."""
        if len(self.workers) == 1:
            rep = self.workers[0].run_cycle(max_segments=max_segments)
            rep.acked = self._all_acked()
            return rep
        with telemetry.span("maintenance/pool_cycle", cat="maintenance",
                            workers=len(self.workers)):
            reps = list(self._pool.map(
                lambda w: w.run_cycle(max_segments=max_segments),
                self.workers))
        total = BackfillReport()
        for rep in reps:
            merge_reports(total, rep, sequential=False)
        total.acked = self._all_acked()
        return total

    def run_until_converged(self, *, max_cycles: int = 1000) -> BackfillReport:
        """Cycle the pool until every shard converged (or no shard can make
        progress).  Totals merge across cycles."""
        total = BackfillReport()
        for _ in range(max_cycles):
            rep = self.run_cycle()
            merge_reports(total, rep)
            if rep.messages == 0 and (
                    rep.pending_after == 0
                    or (rep.segments_backfilled == 0
                        and rep.segments_partial == 0)):
                break
        total.acked = self._all_acked()
        return total

    def _all_acked(self) -> bool:
        """Pool-level ack state: every worker has a target and owes no ack
        (its shard converged and the ack was published)."""
        return all(w._target is not None and not w._ack_pending
                   for w in self.workers)
