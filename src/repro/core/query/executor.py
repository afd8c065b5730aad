"""Physical plan executor — shared-arrangement device plane, batched
dispatch, sharded workers.

The executor turns a ``PhysicalPlan`` into per-segment results with the
same single-dispatch discipline PR 2 brought to ingest, now on the read
side — and, since the shared-arrangement refactor, with ONE device copy of
the data across ALL in-flight queries:

  * ALL ``bitmap``-class segments of a query are matched against the
    query's conjunctive mask set in ONE stacked device dispatch through
    the ``bitmap_filter`` kernels; exactly one counted D2H transfer per
    query brings back the match mask (or, on real accelerators, the
    device-reduced per-segment counts — ``device_counts``);
  * the stacked word-column arrays live in a shared, refcounted,
    epoch-versioned ``ArrangementStore`` (``query.arrangement``): every
    query leases its arrangement RAII-style, concurrent queries over the
    same (segment set, word subset) coalesce onto one device copy — each
    word column is uploaded once per maintenance epoch, not once per
    query — and maintenance swaps *publish a new epoch* instead of
    invalidating anything under a reader;
  * ``fallback``/``full_scan`` segments batch through one fused
    throwaway-DFA dispatch per query (``dfa_scan_fused`` via the ingest
    ``FusedMatcher`` stack) when ``scan_backend`` supports fusion, else
    through the vectorized numpy substring scan per segment;
  * enriched-path results are validated against the meta snapshot their
    classification used; segments swapped mid-query by the maintenance
    plane are re-planned individually.  Full-scan results are returned
    directly — they never read enrichment state, so a concurrent swap
    cannot invalidate them.

``ShardedQueryExecutor`` partitions ``plan.tasks`` by segment identity
across a worker pool: each shard runs its own stacked dispatch against the
shared arrangement plane (leases carry the shard's worker identity, the
same scheme the maintenance plane uses to attribute work) and re-plans
swapped segments independently; the merge step reassembles per-segment
results in plan order, so counters and ``path_class_stats`` aggregate
exactly as in the single-worker path.

``backend="numpy"`` preserves the pre-refactor per-segment numpy execution
(bit tests on single bitmap words, no batching, no sharing) behind the
same planner — the equivalence oracle and the honest baseline lane in
benchmarks.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import numpy as np

from repro.core import faults, telemetry
from repro.core.faults import InjectedCrash
from repro.core.stream_processor import ENRICH_COLUMN
from repro.core.query.arrangement import ArrangementItem, ArrangementStore
from repro.core.query.planner import (BITMAP, FALLBACK, FULL_SCAN,
                                      META_COUNT, POSTINGS, PRUNED,
                                      TEXT_INDEX)
from repro.device import on_cpu

# -- device->host accounting -------------------------------------------------
# The batched bitmap path performs exactly ONE D2H transfer per query; tests
# assert this via ``transfer_count`` — now an alias over the process-wide
# telemetry registry (mirrors core.matcher.transfer_count).
_D2H = telemetry.counter(
    "fluxsieve_query_d2h_total",
    help="Device-to-host transfers on the query plane (one per query).")
_STACKED_DISPATCH = telemetry.counter(
    "fluxsieve_query_stacked_dispatch_total",
    help="Stacked bitmap-class device dispatches.")


def transfer_count() -> int:
    return int(_D2H.value)


def _to_host(x):
    _D2H.inc()
    import jax
    return jax.device_get(x)


def substring_scan(data: np.ndarray, term: str) -> np.ndarray:
    """(N, L) uint8 contains `term` as a byte substring -> (N,) bool."""
    t = term.encode()
    N, L = data.shape
    m = len(t)
    if m == 0 or m > L:
        return np.zeros(N, bool)
    # vectorized first-byte prefilter, then confirm remaining bytes
    acc = data[:, :L - m + 1] == t[0]
    for i in range(1, m):
        acc &= data[:, i:L - m + 1 + i] == t[i]
    return acc.any(axis=1)


# executor-side outcome (not a planner class): the shard serving this
# segment faulted or overran its deadline — the engine reports a partial
# result with per-segment coverage accounting instead of failing the query
FAILED = "failed"

_SHARDS_FAILED = telemetry.counter(
    "fluxsieve_query_shards_failed_total",
    help="Query shards that faulted or overran the per-shard deadline.")


@dataclass
class TaskStats:
    """Per-segment counters, merged into the QueryResult by the engine."""
    scanned: int = 0
    pruned: int = 0
    fallback: int = 0
    bytes_read: int = 0
    fallback_ids: tuple = ()
    path_class: str = ""
    failed: int = 0             # shard faulted/timed out; segment unserved
    failed_ids: tuple = ()


class PlanExecutor:
    """Executes ``PhysicalPlan``s.  ``backend`` selects the bitmap-class
    physical engine: ``numpy`` (pre-refactor per-segment word tests),
    ``ref`` (stacked jnp dispatch), ``pallas`` (stacked Pallas kernel).
    ``scan_backend`` (e.g. ``"dfa_ref"``/``"dfa"``) routes full scans
    through throwaway compiled matchers instead of the numpy substring
    scan (fused-capable backends batch all scan segments into one
    dispatch).  ``device_counts`` selects the device-side per-segment
    count reduction for count-mode queries: ``"auto"`` enables it on real
    accelerators only (on XLA CPU the reduction measurably costs more
    than transferring the mask), ``True``/``False`` force it.
    Thread-safe; ``workers > 1`` scans host-path segments concurrently
    (the intra-query parallelism axis of Figs 6-9)."""

    MAX_SNAPSHOT_RETRIES = 3

    def __init__(self, *, backend: str = "ref", scan_backend: str = None,
                 block_n: int = 1024, workers: int = 1,
                 arrangements: ArrangementStore = None,
                 device_counts="auto"):
        if backend not in ("numpy", "ref", "pallas"):
            raise ValueError(f"unknown executor backend {backend!r}")
        self.backend = backend
        self.scan_backend = scan_backend
        self.block_n = block_n
        self.workers = workers
        self.arrangements = arrangements or ArrangementStore()
        self.device_counts = device_counts
        self._masks = {}                # rule_ids -> device word-bit vector
        self._mask_lock = threading.Lock()
        self._scan_engines = {}         # (query key, fields) -> matchers
        self._scan_fused = {}           # (query key, backend) -> FusedMatcher
        self._scan_lock = threading.Lock()

    # -- entry ---------------------------------------------------------------
    def execute(self, plan, planner, *, cache: bool = True,
                owner: str = "query") -> list:
        """-> [(ids, TaskStats)] parallel to ``plan.tasks``; ids is None
        (pruned), an int (metadata count), or an int32 id array.
        ``owner`` tags arrangement leases (shard worker identity)."""
        tasks = plan.tasks
        results = [None] * len(tasks)
        if self.backend != "numpy":
            idx = [i for i, t in enumerate(tasks) if t.path_class == BITMAP]
            if idx:
                for i, r in zip(idx, self._run_stacked(
                        plan, [tasks[i] for i in idx], cache, owner)):
                    results[i] = r      # None -> snapshot swapped, re-plan
            idx = [i for i, t in enumerate(tasks)
                   if results[i] is None
                   and t.path_class in (FALLBACK, FULL_SCAN)]
            if len(idx) > 1 and self._fused_scan_capable(plan.query):
                for i, r in zip(idx, self._run_scans_batched(
                        plan, [tasks[i] for i in idx], cache)):
                    results[i] = r

        remaining = [i for i in range(len(tasks)) if results[i] is None]

        def one(i):
            return self._run_task(plan, planner, tasks[i], cache)

        if self.workers > 1 and len(remaining) > 1:
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(self.workers) as pool:
                for i, r in zip(remaining, pool.map(one, remaining)):
                    results[i] = r
        else:
            for i in remaining:
                results[i] = one(i)
        return results

    # -- stacked bitmap class (single device dispatch, single D2H) -----------
    def _use_device_counts(self) -> bool:
        if self.device_counts == "auto":
            self.device_counts = not on_cpu()
        return bool(self.device_counts)

    def _run_stacked(self, plan, tasks, cache: bool, owner: str) -> list:
        from repro.kernels.bitmap_filter.ops import bitmap_query_words

        # the plan's word-sliced encoding: one (word, bit) pair per
        # single-rule predicate.  Traffic per hot query is N*P words (what
        # the numpy path reads), not N*W.
        words, bits_np = plan.flux.word_slices()
        stats = [TaskStats(path_class=BITMAP) for _ in tasks]
        # tokens are read here, BEFORE any host column load, so a racing
        # maintenance swap can only pool new data under an already-dead
        # token — the snapshot validation below decides result validity
        items = [ArrangementItem(
            token=t.seg.meta_token(), num_records=int(t.seg.num_records),
            load=self._host_loader(t.seg, cache, st))
            for t, st in zip(tasks, stats)]
        with telemetry.span("query/arrangement", cat="query",
                            segments=len(tasks)) as span:
            bits = self._device_bits(plan.flux.rule_ids, bits_np)
            if cache:
                lease = self.arrangements.lease(items, words,
                                                block_n=self.block_n,
                                                owner=owner)
            else:   # cold run: private build, pays (and accounts) its I/O
                lease = self.arrangements.build_ephemeral(
                    items, words, block_n=self.block_n, owner=owner)
            span.set(hit=lease.hit)
        try:
            arr = lease.arrangement
            copy_mode = plan.query.mode == "copy"
            # retention straddlers need row ids (the engine filters them by
            # timestamp), so device-side count reduction is off for them
            any_cutoff = any(t.cutoff is not None for t in tasks)
            with_counts = (not copy_mode and not any_cutoff
                           and self._use_device_counts())
            with telemetry.span("query/stacked_dispatch", cat="query",
                                segments=len(tasks), owner=owner):
                match_dev, counts_dev = bitmap_query_words(
                    arr.stack, bits, arr.row_seg, num_segments=len(tasks),
                    backend="pallas" if self.backend == "pallas" else "ref",
                    block_n=self.block_n, with_counts=with_counts)
            _STACKED_DISPATCH.inc()
            # the ONE counted D2H per query: on accelerators the
            # device-side one-hot count reduction shrinks it from N bytes
            # to S ints; on XLA CPU the mask transfer is the measured win
            with telemetry.span("query/device_wait", cat="query",
                                counts=with_counts):
                if with_counts:
                    counts = np.asarray(_to_host(counts_dev))[:len(tasks)]
                    match = None
                else:
                    match = _to_host(match_dev)
            lens = arr.lens
        finally:
            lease.release()
        out, off = [], 0
        for slot, (t, st, n) in enumerate(zip(tasks, stats, lens)):
            if t.seg.meta is not t.meta:
                out.append(None)        # swapped mid-query: re-plan this one
            else:
                st.scanned += 1
                if match is None:
                    ids = int(counts[slot])
                elif copy_mode or t.cutoff is not None:
                    ids = np.flatnonzero(match[off:off + n]).astype(np.int32)
                else:
                    ids = int(np.count_nonzero(match[off:off + n]))
                out.append((ids, st))
            off += n
        return out

    def _host_loader(self, seg, cache: bool, stats: TaskStats):
        """Host bitmap read for an arrangement build, accounting disk bytes
        to the query that actually triggered the upload."""
        def load():
            in_mem = ENRICH_COLUMN in seg._columns
            host = seg.column(ENRICH_COLUMN, cache=cache)
            if not in_mem:
                stats.bytes_read += host.nbytes
            return np.asarray(host)
        return load

    def _device_bits(self, rule_ids: tuple, bits_np: np.ndarray):
        """Device-resident per-predicate word masks, cached per rule-id
        tuple (content is a pure function of it)."""
        import jax.numpy as jnp
        with self._mask_lock:
            bits = self._masks.get(rule_ids)
        if bits is None:
            bits = jnp.asarray(bits_np)
            with self._mask_lock:
                if len(self._masks) > 64:       # bound growth
                    self._masks.clear()
                self._masks[rule_ids] = bits
        return bits

    # -- batched fallback / full scans (one fused DFA dispatch per query) ----
    def _fused_scan_capable(self, query) -> bool:
        from repro.core.matcher import FUSED_BACKENDS
        return (self.scan_backend in FUSED_BACKENDS
                and all(t for _, t in query.terms))

    def _run_scans_batched(self, plan, tasks, cache: bool) -> list:
        """ALL fallback/full-scan segments of one query, stacked on N and
        matched in one throwaway-DFA fused dispatch (the scan-path analogue
        of the stacked bitmap class): per-field text columns concatenate
        across segments, ``dfa_scan_fused`` runs once, and per-segment ids
        slice out of the combined bitmap on the host.  Full scans never
        read enrichment state, so results return directly — no snapshot
        re-validation (same contract as the per-segment path)."""
        from repro.core.enrichment import rule_mask
        query = plan.query
        stats = []
        for t in tasks:
            st = TaskStats(path_class=t.path_class, scanned=1)
            if t.path_class == FALLBACK:
                st.fallback = 1
                st.fallback_ids = (t.seg.segment_id,)
            stats.append(st)
        fused = self._scan_fused_matcher(query)
        fields = tuple(sorted({f for f, _ in query.terms}))
        lens = [int(t.seg.num_records) for t in tasks]
        cols = {}
        for f in fields:
            parts = [np.asarray(self._read(t.seg, f, cache, st))
                     for t, st in zip(tasks, stats)]
            L = max(p.shape[1] for p in parts)
            parts = [np.pad(p, ((0, 0), (0, L - p.shape[1])))
                     if p.shape[1] < L else p for p in parts]
            cols[f] = np.concatenate(parts)
        bm, _ = fused.match_batch(cols, fields, sum(lens)).to_host()
        need = rule_mask(range(len(query.terms)), len(query.terms))
        k = min(bm.shape[1], len(need))
        keep = ((bm[:, :k] & need[None, :k]) == need[None, :k]).all(axis=1)
        out, off = [], 0
        for st, n in zip(stats, lens):
            out.append((np.flatnonzero(keep[off:off + n]).astype(np.int32),
                        st))
            off += n
        return out

    def _scan_fused_matcher(self, query):
        from repro.core.matcher import FusedMatcher
        key = (query.key(), self.scan_backend)
        with self._scan_lock:
            fused = self._scan_fused.get(key)
        if fused is None:
            bundle = self._scan_bundle(query)
            fused = FusedMatcher(bundle, backend=self.scan_backend,
                                 block_n=self.block_n)
            with self._scan_lock:
                if len(self._scan_fused) > 64:
                    self._scan_fused.clear()
                self._scan_fused[key] = fused
        return fused

    # -- per-segment paths ---------------------------------------------------
    def _run_task(self, plan, planner, task, cache: bool) -> tuple:
        query = plan.query
        if task.path_class in (TEXT_INDEX, FULL_SCAN):
            stats = TaskStats(path_class=task.path_class)
            if task.path_class == TEXT_INDEX:
                return self._text_index(query, task.seg, cache, stats), stats
            return self._full_scan(query, task.seg, cache, stats), stats
        # enriched-path classes: snapshot-validate-retry.  The maintenance
        # plane can swap a sealed segment's enrichment between classification
        # and our read; everything here was evaluated against ONE meta
        # snapshot, so confirm the segment still carries it, re-plan on a
        # swap, and after repeated swaps fall back to the full scan.
        t = task
        for _ in range(self.MAX_SNAPSHOT_RETRIES):
            stats = TaskStats(path_class=t.path_class)
            if t.path_class == FALLBACK:
                # full scans never read enrichment state: return directly,
                # no re-validation — also the terminal state of a re-plan
                stats.fallback += 1
                stats.fallback_ids += (t.seg.segment_id,)
                return self._full_scan(query, t.seg, cache, stats), stats
            ids = self._enriched(plan, t, cache, stats)
            # non-flux plans only reach here via retention-expired PRUNED
            # tasks, which read nothing — no snapshot to invalidate
            if t.seg.meta is t.meta or plan.flux is None:
                return ids, stats
            t = planner.classify(t.seg, query, plan.flux, cache)
        stats = TaskStats(path_class=FALLBACK, fallback=1,
                          fallback_ids=(t.seg.segment_id,))
        return self._full_scan(query, t.seg, cache, stats), stats

    def _enriched(self, plan, task, cache: bool, stats: TaskStats):
        if task.path_class == PRUNED:
            stats.pruned += 1
            return None
        stats.scanned += 1
        if task.path_class == META_COUNT:
            return task.count
        if task.path_class == POSTINGS:
            ids = task.postings[0]
            for p in task.postings[1:]:
                ids = np.intersect1d(ids, p, assume_unique=True)
                if not len(ids):
                    break
            return ids
        # BITMAP, one segment: the pre-refactor numpy word/bit test — also
        # the retry path after a stacked-batch snapshot invalidation
        bm = self._read(task.seg, ENRICH_COLUMN, cache, stats)
        keep = None
        for rid in plan.flux.rule_ids:
            # test ONE word column + bit, not the full (N, W) mask product
            m = (bm[:, rid // 32] >> np.uint32(rid % 32)) & np.uint32(1)
            keep = m.astype(bool) if keep is None else (keep & m.astype(bool))
        return np.flatnonzero(keep)

    def _text_index(self, query, seg, cache: bool, stats: TaskStats):
        stats.scanned += 1
        ids = None
        for fieldname, term in query.terms:
            idx = seg.text_index(fieldname, cache=cache)
            posting = idx.get(term, np.zeros(0, np.int32))
            ids = posting if ids is None else np.intersect1d(
                ids, posting, assume_unique=True)
            if not len(ids):
                break
        return ids

    # -- full scans ----------------------------------------------------------
    def _full_scan(self, query, seg, cache: bool, stats: TaskStats):
        stats.scanned += 1
        if self.scan_backend is not None and all(t for _, t in query.terms):
            return self._full_scan_dfa(query, seg, cache, stats)
        mask = None
        for fieldname, term in query.terms:
            col = self._read(seg, fieldname, cache, stats)
            m = substring_scan(col, term)
            mask = m if mask is None else (mask & m)
        return np.flatnonzero(mask)

    def _full_scan_dfa(self, query, seg, cache: bool, stats: TaskStats):
        """Consistency-fallback scan through the fused matcher stack: query
        terms compile (once, cached per query key) into throwaway literal
        rules — one bit per term — and the raw text columns run through the
        same DFA machinery the ingest plane uses."""
        from repro.core.enrichment import rule_mask
        matchers = self._scan_matchers(query)
        bm = None
        for fieldname, eng in matchers.items():
            col = self._read(seg, fieldname, cache, stats)
            sub = np.asarray(eng.match(col))
            bm = sub if bm is None else (bm | sub)
        need = rule_mask(range(len(query.terms)), len(query.terms))
        keep = ((bm & need[None, :bm.shape[1]])
                == need[None, :bm.shape[1]]).all(axis=1)
        return np.flatnonzero(keep)

    def _scan_bundle(self, query):
        from repro.core.matcher import compile_bundle
        from repro.core.patterns import Rule, RuleSet, escape
        rules = tuple(Rule(i, f"q{i}", escape(term), fields=(f,))
                      for i, (f, term) in enumerate(query.terms))
        fields = tuple(sorted({f for f, _ in query.terms}))
        return compile_bundle(RuleSet(rules), fields)

    def _scan_matchers(self, query) -> dict:
        from repro.core.matcher import build_matchers
        key = (query.key(), self.scan_backend)
        with self._scan_lock:
            matchers = self._scan_engines.get(key)
        if matchers is None:
            matchers = build_matchers(self._scan_bundle(query),
                                      backend=self.scan_backend,
                                      block_n=self.block_n)
            with self._scan_lock:
                if len(self._scan_engines) > 64:    # bound growth: ad-hoc
                    self._scan_engines.clear()      # query shapes are open
                self._scan_engines[key] = matchers
        return matchers

    def _read(self, seg, name: str, cache: bool, stats: TaskStats):
        in_mem = name in seg._columns
        col = seg.column(name, cache=cache)
        if not in_mem:
            stats.bytes_read += col.nbytes
        return col


class ShardedQueryExecutor:
    """Sharded query workers over the shared arrangement plane.

    ``plan.tasks`` partition across shards by record-count-weighted
    greedy assignment (``affinity="weighted"``, deterministic so repeated
    queries keep each shard's arrangement hot; ``"modulo"`` selects the
    legacy ``segment_id % shards`` scheme for A/B comparison)
    onto a persistent worker pool; every shard runs its own stacked
    dispatch — leasing from the SAME ``ArrangementStore``, so sharding
    multiplies concurrency, not device copies — and re-plans segments the
    maintenance plane swapped under it independently of its siblings.  The
    merge step reassembles per-segment ``(ids, TaskStats)`` into plan
    order, so counts, counters, and ``path_class_stats`` aggregate exactly
    as in the single-worker executor.

    Worker identity reuses the maintenance plane's scheme
    (``{worker_id}/shard-{i}``): arrangement leases are attributed per
    shard, so a leak or a pinned epoch names the worker that owes it.

    ``deadline_s`` bounds the whole query's shard joins: a shard that
    faults or has not produced its results by the deadline is marked
    FAILED — its segments return ``(None, TaskStats(failed=1, ...))``
    markers and the engine degrades to a *partial* result with coverage
    accounting, instead of one slow or broken shard wedging the query."""

    def __init__(self, executor: PlanExecutor, *, shards: int = 4,
                 worker_id: str = "query-0", deadline_s: float = None,
                 affinity: str = "weighted"):
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        self.executor = executor
        self.shards = shards
        self.worker_id = worker_id
        self.deadline_s = deadline_s
        self.affinity = affinity    # shard_tasks scheme: weighted | modulo
        self.worker_idents = tuple(f"{worker_id}/shard-{i}"
                                   for i in range(shards))
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(
            max_workers=shards, thread_name_prefix=f"{worker_id}-shard")

    def close(self) -> None:
        """Shut the shard worker pool down (idle threads exit).  Called on
        finalization too, so churning engines does not accumulate
        process-lifetime threads."""
        self._pool.shutdown(wait=False)

    def __del__(self):
        self.close()

    # mirror the wrapped executor's tuning surface for callers/tests
    @property
    def backend(self) -> str:
        return self.executor.backend

    @property
    def arrangements(self) -> ArrangementStore:
        return self.executor.arrangements

    def execute(self, plan, planner, *, cache: bool = True,
                owner: str = None) -> list:
        tasks = plan.tasks
        shard_idx = plan.shard_tasks(self.shards, affinity=self.affinity)
        if len(shard_idx) <= 1 and self.deadline_s is None:
            return self.executor.execute(plan, planner, cache=cache,
                                         owner=owner or self.worker_idents[0])

        def run_shard(k, idx):
            faults.fire("query.shard", shard=k,
                        worker=self.worker_idents[k % self.shards])
            sub = plan.subplan(idx)
            return self.executor.execute(
                sub, planner, cache=cache,
                owner=self.worker_idents[k % self.shards])

        futures = [self._pool.submit(run_shard, k, idx)
                   for k, idx in enumerate(shard_idx)]
        results = [None] * len(tasks)
        deadline = (time.monotonic() + self.deadline_s
                    if self.deadline_s is not None else None)
        for k, (idx, fut) in enumerate(zip(shard_idx, futures)):
            try:
                if deadline is None:
                    shard_results = fut.result()
                else:
                    shard_results = fut.result(
                        timeout=max(0.0, deadline - time.monotonic()))
            except InjectedCrash:
                raise           # a simulated kill is never a partial result
            except Exception as e:  # noqa: BLE001 — degrade to partial
                # includes futures.TimeoutError (deadline overrun); the
                # overrunning worker thread finishes in the background —
                # only this query stops waiting for it
                _SHARDS_FAILED.inc()
                telemetry.emit("shard_failed", plane="query", shard=k,
                               segments=len(idx),
                               error=f"{type(e).__name__}: {e}")
                for i in idx:
                    results[i] = (None, TaskStats(
                        path_class=FAILED, failed=1,
                        failed_ids=(tasks[i].seg.segment_id,)))
                continue
            for i, r in zip(idx, shard_results):
                results[i] = r
        return results
