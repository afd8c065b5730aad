"""BackfillWorker — retroactive re-enrichment of sealed segments.

FluxSieve's consistency rule (paper §3.4 step 4) makes enrichment safe but
pessimistic: a segment sealed before a rule activated serves that rule via
full scan forever.  The backfill worker closes the gap off the ingest path
(Shared Arrangements' shared index maintenance / Fluid ETL's incremental
backfill, applied to the enrichment column):

  1. it consumes engine-update notifications on its OWN control-bus topic
     (``SEGMENT_MAINTENANCE``) with its own consumer-group offsets, fetching
     and validating the compiled artifact exactly like a stream processor;
  2. per sealed segment it diffs the activated ruleset against the segment's
     ``rule_idents`` (rule *content* identities, so changed patterns are
     re-matched, not trusted) and matches only the **delta** rules against
     the segment's text columns, reusing the compiled-matcher stack;
  3. it atomically rewrites the segment's ``rule_bitmap`` column plus every
     derived artifact — ``rule_bitmap_any`` zone map, ``rule_counts``, rule
     postings, ``rules_known`` — via ``Segment.apply_update``, so concurrent
     queries see either the fully-old or fully-new enrichment;
  4. once no sealed segment in ITS SHARD lags the active version it
     publishes an ack on ``MAINTENANCE_ACKS`` (the updater's
     ``await_maintenance`` watches it, one ack per worker id).

Maintenance plane v2 — distribution and durability:

  * **Sharding**: a worker owns the segments ``shard_of(segment_id,
    num_shards) == shard_index``; a ``MaintenanceWorkerPool`` runs N such
    workers over one store, each with its own consumer-group offsets
    (at-least-once delivery per worker, so a crashed worker's replacement
    re-reads the topic from its own committed offset);
  * **Leases + epoch fencing** (``maintenance.lease``): every install is
    guarded by a per-segment lease whose epoch is the fencing token carried
    into ``Segment.apply_update(fence=...)`` — two workers can never
    interleave writes on one segment, and a crashed worker's lease expires
    instead of wedging its shard;
  * **Incremental checkpointing**: long segments are matched in row-range
    passes (``rows_per_pass``); each partial pass persists a per-segment
    high-water mark + the partially rebuilt bitmap (atomically, next to the
    spill files), so a worker restart or a mid-segment budget cut resumes
    matching from the watermark instead of row 0.  The checkpoint is keyed
    on the target (version + delta), so a moved target invalidates it.

Invariant: a query result is byte-identical whether a segment is served via
backfilled bitmap, postings, metadata counts, or full-scan fallback — and
the install itself stays all-or-nothing (checkpoints stage work *outside*
the segment's visible artifacts; only the final ``apply_update`` swaps).
"""
from __future__ import annotations

import os
import time
import zipfile
from dataclasses import dataclass, field

import numpy as np

from repro.core import faults, telemetry
from repro.core.automaton import words_for_rules
from repro.core.control_plane import (ControlBus, MAINTENANCE_ACKS,
                                      SEGMENT_MAINTENANCE)
from repro.core.enrichment import rule_mask
from repro.core.maintenance.lease import (FencedWriteError, LeaseManager,
                                          shard_of)
from repro.core.matcher import EngineBundle, build_matchers, compile_bundle
from repro.core.object_store import ObjectRef, ObjectStore
from repro.core.patterns import RuleSet, ruleset_idents
from repro.core.query.store import (SegmentStore, derive_enrichment_meta,
                                    pack_known_bitmap)
from repro.core.stream_processor import ENRICH_COLUMN

# per-segment backfill checkpoint, stored NEXT TO the spill files (swapped
# atomically via tmp+os.replace); never part of the segment's visible state
CKPT_NAME = "backfill.ckpt.npz"

_BF_SEGMENTS = telemetry.counter(
    "fluxsieve_maintenance_segments_backfilled_total",
    help="Segments fully re-enriched by the backfill plane.")
_BF_ROWS = telemetry.counter(
    "fluxsieve_maintenance_rows_matched_total",
    help="Rows re-matched by backfill passes.")
_BF_ROWS_RESUMED = telemetry.counter(
    "fluxsieve_maintenance_rows_resumed_total",
    help="Rows skipped thanks to a backfill checkpoint resume.")
_BF_BYTES = telemetry.counter(
    "fluxsieve_maintenance_bytes_rewritten_total",
    help="Enrichment bytes rewritten by backfill installs.")
_BF_CHECKPOINTS = telemetry.counter(
    "fluxsieve_maintenance_checkpoints_total",
    help="Partial backfill passes persisted as checkpoints.")


@dataclass(frozen=True)
class _Target:
    """Latest activated ruleset the store should converge to."""
    version: str
    ruleset: RuleSet
    idents: dict            # str(rule_id) -> content identity


@dataclass
class BackfillReport:
    version: str = ""
    messages: int = 0
    segments_backfilled: int = 0
    segments_skipped: int = 0   # sealed w/o enrichment column (gauge): can
                                # never converge, served by scan paths only
    segments_failed: int = 0    # raised during backfill; retried next cycle
    segments_partial: int = 0   # row-budget cut mid-segment; checkpointed
    segments_contended: int = 0  # lease held (or fenced) by another worker
    errors: list = field(default_factory=list)   # (segment_id, error) pairs
    records: int = 0
    rows_matched: int = 0       # rows actually re-matched this cycle (a
                                # checkpoint resume makes this < records)
    rows_resumed: int = 0       # rows skipped thanks to a checkpoint
    bytes_rewritten: int = 0
    seconds: float = 0.0
    pending_after: int = 0      # pending in THIS worker's shard
    acked: bool = False


def merge_reports(total: BackfillReport, rep: BackfillReport,
                  *, sequential: bool = True) -> BackfillReport:
    """Accumulate ``rep`` into ``total``.  ``sequential`` merges cycles of
    ONE worker over time (gauges take the latest value); the pool merges
    same-cycle reports of MANY workers (gauges sum across shards)."""
    total.version = rep.version or total.version
    total.messages += rep.messages
    total.segments_backfilled += rep.segments_backfilled
    total.segments_failed += rep.segments_failed
    total.segments_partial += rep.segments_partial
    total.segments_contended += rep.segments_contended
    total.errors.extend(rep.errors[:max(0, 8 - len(total.errors))])
    total.records += rep.records
    total.rows_matched += rep.rows_matched
    total.rows_resumed += rep.rows_resumed
    total.bytes_rewritten += rep.bytes_rewritten
    total.seconds += rep.seconds
    if sequential:
        total.segments_skipped = rep.segments_skipped
        total.pending_after = rep.pending_after
        total.acked = total.acked or rep.acked
    else:
        total.segments_skipped = max(total.segments_skipped,
                                     rep.segments_skipped)
        total.pending_after += rep.pending_after
    return total


class BackfillWorker:
    """One maintenance-plane worker (``run_cycle`` is its poll loop body).

    ``shard_index``/``num_shards`` restrict the worker to its hash shard of
    the segment space (``lease.shard_of``); ``leases`` guards every install
    with a fenced per-segment lease; ``rows_per_pass`` bounds how many rows
    one cycle matches per segment (the rest is checkpointed and resumed).
    ``matcher_cache`` lets a ``MaintenanceWorkerPool`` share compiled delta
    matchers across workers (compiled engines are immutable/thread-safe)."""

    def __init__(self, store: SegmentStore, bus: ControlBus,
                 object_store: ObjectStore, *, worker_id: str = "maint-0",
                 scheduler=None, backend: str = "dfa_ref",
                 block_n: int = 256,
                 shard_index: int = 0, num_shards: int = 1,
                 leases: LeaseManager = None, rows_per_pass: int = None,
                 matcher_cache: dict = None):
        self.store = store
        self.bus = bus
        self.object_store = object_store
        self.worker_id = worker_id
        self.scheduler = scheduler
        self.backend = backend
        self.block_n = block_n
        if not 0 <= shard_index < max(num_shards, 1):
            raise ValueError(f"shard_index {shard_index} out of range for "
                             f"{num_shards} shards")
        self.shard_index = shard_index
        self.num_shards = max(num_shards, 1)
        self.leases = leases
        self.rows_per_pass = rows_per_pass
        self._target: _Target = None
        # each installed target owes exactly one convergence ack — keyed on
        # installation, not version string, so rolling BACK to a previously
        # acked version still acks once re-converged
        self._ack_pending = False
        self._nacked: set = set()       # offsets already nacked (no spam)
        self._seen_upto = 0             # poll high-water mark (retries are
                                        # not "new" messages to callers)
        self._failed_ids: set = set()   # segments whose last backfill raised
                                        # (deprioritized, retried when idle)
        # incremental pending tracking (single maintenance writer): a full
        # O(segments x rules) ident rescan happens only on target change;
        # steady-state cycles diff just the newly sealed segments
        self._pending_ids: set = None   # None = needs full rescan
        self._scanned_upto = 0          # segment-id high-water mark
        # (version, delta ids, fields) -> dict; shareable across a THREAD
        # pool (compiled engines are immutable/thread-safe).  NOT shared
        # across processes: a ProcessMaintenancePool worker owns a
        # private cache and warms it once per target version
        # (``warm_matchers``) instead of silently recompiling.
        self._matchers: dict = matcher_cache if matcher_cache is not None \
            else {}
        self._warmed_version = None     # target version last warmed for
        self._mem_ckpts: dict = {}      # sid -> (key, hwm, bm) for segments
                                        # without a spill path

    @property
    def worker_ids(self) -> tuple:
        """Worker identities to await acks from (pool-compatible shape)."""
        return (self.worker_id,)

    def owns(self, segment_id: int) -> bool:
        """Shard ownership: this worker backfills (and acks) only its hash
        shard of the segment space."""
        return shard_of(segment_id, self.num_shards) == self.shard_index

    # -- control topology --------------------------------------------------
    def poll_target(self) -> int:
        """Consume engine-update notifications; keep the newest valid target.

        Each notification supersedes the last — backfill converges to the
        latest ruleset, intermediate versions need no historical pass — so
        the backlog is walked newest-first and only the first message whose
        artifact fetches and validates is deserialized; older (superseded)
        messages are committed without touching the object store.  A fresh
        worker group replaying a long topic history therefore does one
        fetch, not one per historical version.

        At-least-once on every candidate that has not been superseded by a
        successful install: offsets are committed only up to the installed
        message, because a message is superseded only once some NEWER
        message actually installs.  In particular, when the newest
        notification is permanently invalid and an older one failed
        transiently, nothing is committed — the older candidate stays
        fetchable and is retried next cycle instead of being silently
        forfeited (duplicate nacks stay suppressed via ``_nacked``).

        Restart recovery: a worker that installed a target, committed its
        offset, and then CRASHED would otherwise never see that
        notification again — its replacement (same worker id, same group)
        polls past the committed offset and finds nothing.  The committed
        offset gates delivery accounting, not target durability: a worker
        with no target re-derives the newest valid one from the raw topic
        history, and owes a convergence ack for it — so a mid-backfill
        crash still ends in exactly the acks the updater awaits once the
        replacement (resuming from checkpoints) converges."""
        group = f"maintenance/{self.worker_id}"
        recovering = False
        msgs = self.bus.poll(SEGMENT_MAINTENANCE, group,
                             max_messages=1_000_000)
        if not msgs and self._target is None:
            msgs = self.bus.messages(SEGMENT_MAINTENANCE, 0)
            recovering = True
            if msgs:
                telemetry.emit("target_recovered", plane="maintenance",
                               worker=self.worker_id,
                               replayed=len(msgs))
        if not msgs:
            return 0
        installed_offset = None
        for msg in reversed(msgs):
            try:
                ref = ObjectRef.from_dict(msg.value["object_ref"])
                data = self.object_store.get(ref, verify=True)
                bundle = EngineBundle.deserialize(data, verify=True)
                if bundle.version != msg.value["engine_version"]:
                    raise ValueError("version mismatch")
                if bundle.checksum() != msg.value["checksum"]:
                    raise ValueError("bundle checksum != notification checksum")
                ruleset = bundle.ruleset()
                self._target = _Target(version=bundle.version, ruleset=ruleset,
                                       idents=ruleset_idents(ruleset))
                self._evict_matchers(bundle.version)
                self._ack_pending = True
                self._pending_ids = None    # target moved: full rescan
                installed_offset = msg.offset
                break
            except Exception as e:  # noqa: BLE001 — nack, try the next-newest
                if msg.offset not in self._nacked:
                    self._nacked.add(msg.offset)
                    self.bus.publish(MAINTENANCE_ACKS, {
                        "worker": self.worker_id,
                        "engine_version": msg.value.get("engine_version"),
                        "ok": False, "error": str(e),
                        "object_ref": msg.value.get("object_ref"),
                    })
        newest = msgs[-1].offset
        if installed_offset is not None:
            # everything at/below the install is superseded; failed NEWER
            # candidates stay uncommitted and are retried next cycle
            # (idempotent under recovery: commit never rewinds offsets)
            self.bus.commit(SEGMENT_MAINTENANCE, group, installed_offset)
        seen = sum(1 for m in msgs if m.offset >= self._seen_upto)
        self._seen_upto = max(self._seen_upto, newest + 1)
        return 0 if recovering else seen    # replay is not new delivery

    def set_target(self, ruleset: RuleSet) -> None:
        """Direct (bus-less) targeting, for embedded/offline use."""
        self._target = _Target(version=ruleset.version_hash(), ruleset=ruleset,
                               idents=ruleset_idents(ruleset))
        self._evict_matchers(self._target.version)
        self._ack_pending = True
        self._pending_ids = None

    def _evict_matchers(self, current_version: str) -> None:
        """Bound the compiled-matcher cache on target change WITHOUT
        wiping it: keys are version-scoped, so stale-version engines are
        merely unreachable, not wrong.  Evicting eagerly would defeat the
        pool-shared cache (worker B's install must not discard engines
        worker A just compiled for the SAME version) — so stale versions
        are dropped only once the cache actually grows."""
        if len(self._matchers) <= 32:
            return
        for k in [k for k in list(self._matchers)
                  if k[0] != current_version]:
            self._matchers.pop(k, None)

    # -- delta computation -------------------------------------------------
    def segment_delta(self, seg) -> tuple:
        """-> (delta_ids, removed_ids): rules to (re-)match vs rules whose
        bits/idents must be cleared.  Empty + empty == segment converged."""
        t = self._target
        seg_idents = seg.meta.get("rule_idents") or {}
        delta = [int(rid) for rid, ident in t.idents.items()
                 if seg_idents.get(rid) != ident]
        removed = [int(rid) for rid in seg_idents if rid not in t.idents]
        return sorted(delta), sorted(removed)

    def pending_segments(self) -> list:
        """Sealed, enrichment-bearing segments OF THIS WORKER'S SHARD not
        yet at the target (exact, full rescan)."""
        if self._target is None:
            return []
        return [seg for seg in list(self.store.segments)
                if self._segment_pending(seg)]

    def _segment_pending(self, seg) -> bool:
        if not self.owns(seg.segment_id):
            return False    # another shard's worker converges (and acks) it
        if ENRICH_COLUMN not in seg.meta["columns"]:
            return False
        delta, removed = self.segment_delta(seg)
        return bool(delta or removed)

    def _refresh_pending(self) -> list:
        """Incrementally maintained pending list: exact under the single
        maintenance-writer assumption, O(new segments) per steady-state
        cycle instead of O(all segments)."""
        segs = list(self.store.segments)
        ids = {s.segment_id for s in segs}
        if self._pending_ids is None:
            self._pending_ids = {s.segment_id for s in segs
                                 if self._segment_pending(s)}
        else:
            for s in segs:
                if (s.segment_id >= self._scanned_upto
                        and self._segment_pending(s)):
                    self._pending_ids.add(s.segment_id)
            self._pending_ids &= ids       # compacted-away segments
        self._scanned_upto = max((i + 1 for i in ids), default=0)
        return [s for s in segs if s.segment_id in self._pending_ids]

    # -- data plane --------------------------------------------------------
    def run_cycle(self, *, max_segments: int = None) -> BackfillReport:
        """One maintenance cycle: poll control topic, backfill up to the
        scheduler budget (hottest segments first), ack when converged."""
        with telemetry.span("maintenance/backfill_cycle", cat="maintenance",
                            worker=self.worker_id):
            rep = self._run_cycle(max_segments=max_segments)
        _BF_SEGMENTS.inc(rep.segments_backfilled)
        _BF_ROWS.inc(rep.rows_matched)
        _BF_ROWS_RESUMED.inc(rep.rows_resumed)
        _BF_BYTES.inc(rep.bytes_rewritten)
        return rep

    def _run_cycle(self, *, max_segments: int = None) -> BackfillReport:
        rep = BackfillReport()
        t0 = time.perf_counter()
        rep.messages = self.poll_target()
        if self._target is None:
            rep.seconds = time.perf_counter() - t0
            return rep
        rep.version = self._target.version
        candidates = self._refresh_pending()
        if self._warmed_version != self._target.version:
            # warm the compiled-matcher cache ONCE per installed target:
            # every (delta, fields) engine this worker's shard will need is
            # compiled up front, so per-cycle passes only ever hit the
            # cache.  In the process model each worker owns its cache, so
            # without an explicit warm the compile cost would repeat
            # per-segment-shape per worker silently inside the timed pass.
            self.warm_matchers(candidates)
        # a permanently failing segment must not starve healthy ones under a
        # tight budget: previously-failed segments only get budget once
        # everything else has converged
        fresh = [s for s in candidates
                 if s.segment_id not in self._failed_ids]
        todo = fresh or candidates
        if self.scheduler is not None:
            todo = self.scheduler.plan_cycle(todo)
        if max_segments is not None:
            todo = todo[:max_segments]
        healed = []
        for seg in todo:
            # lease the segment before touching it: sharding makes overlap
            # unlikely, the lease makes it impossible — and the fencing
            # token below makes even a lease we LOST mid-write harmless
            lease = None
            if self.leases is not None:
                lease = self.leases.acquire(seg.segment_id, self.worker_id)
                if lease is None:
                    rep.segments_contended += 1
                    continue        # held elsewhere; stays pending, retried
            fence = self.leases.fence(lease) if lease is not None else None
            # per-segment isolation: one bad segment (corrupt spill file,
            # truncated column) must not crash the worker or stall the rest.
            # A failed segment stays in the pending set — so no ack happens
            # while it lags — and is retried next cycle; a half-applied
            # phase-1 withdraw is safe (queries fall back to scanning).
            try:
                state = self.backfill_segment(
                    seg, max_rows=self._rows_budget(), fence=fence,
                    report=rep)
            except FencedWriteError:
                # lost the lease race mid-write: the successor owns the
                # segment now; nothing was mutated (the fence fires before
                # the first byte), so just leave it to the new holder
                rep.segments_contended += 1
                continue
            except Exception as e:  # noqa: BLE001
                rep.segments_failed += 1
                self._failed_ids.add(seg.segment_id)
                if len(rep.errors) < 8:
                    rep.errors.append((seg.segment_id, str(e)))
                continue
            finally:
                if lease is not None:
                    self.leases.release(lease)
            if state == "partial":
                rep.segments_partial += 1   # checkpointed; resumes next cycle
            elif state == "done":
                rep.segments_backfilled += 1
                rep.records += seg.num_records
                rep.bytes_rewritten += seg.nbytes([ENRICH_COLUMN])
                self._failed_ids.discard(seg.segment_id)
                self._pending_ids.discard(seg.segment_id)
                healed.append(seg.segment_id)
        if healed and self.scheduler is not None:
            # backfill-aware pruning stats: installed segments no longer
            # serve fallback scans — drop their stale heat so the next
            # cycle prioritizes segments still burning query time
            self.scheduler.notify_backfilled(healed)
        # sealed segments with no enrichment column can never converge —
        # surface them instead of silently treating them as done
        rep.segments_skipped = sum(
            1 for seg in list(self.store.segments)
            if ENRICH_COLUMN not in seg.meta["columns"])
        rep.pending_after = len(self._pending_ids)
        if rep.pending_after == 0 and self._ack_pending:
            self.bus.publish(MAINTENANCE_ACKS, {
                "worker": self.worker_id,
                "engine_version": self._target.version,
                "ok": True,
                "segments": len(self.store.segments),
            })
            self._ack_pending = False
            rep.acked = True
            telemetry.emit("convergence_ack", plane="maintenance",
                           worker=self.worker_id,
                           version=self._target.version)
        rep.seconds = time.perf_counter() - t0
        return rep

    def _rows_budget(self):
        """Per-segment row budget for one pass: the worker's own
        ``rows_per_pass`` or the scheduler policy's
        ``max_rows_per_segment_pass`` (whichever is set; worker wins)."""
        if self.rows_per_pass is not None:
            return self.rows_per_pass
        if self.scheduler is not None:
            return getattr(self.scheduler.policy,
                           "max_rows_per_segment_pass", None)
        return None

    def run_until_converged(self, *, max_cycles: int = 1000) -> BackfillReport:
        """Drain: cycle until no sealed segment in this worker's shard lags
        the target.  Returns the totals across all cycles run."""
        total = BackfillReport()
        for _ in range(max_cycles):
            rep = self.run_cycle()
            merge_reports(total, rep)
            if rep.messages == 0 and (
                    rep.pending_after == 0
                    or (rep.segments_backfilled == 0
                        and rep.segments_partial == 0)):
                # converged — or stuck (every remaining segment failing or
                # contended); don't spin max_cycles on a permanently bad
                # segment.  Partial passes ARE progress: keep cycling.
                break
        return total

    def backfill_segment(self, seg, *, max_rows: int = None, fence=None,
                         report: BackfillReport = None) -> str:
        """Re-enrich one sealed segment to the target ruleset.  Matches only
        the delta rules, then atomically swaps bitmap + zone maps + counts +
        postings + coverage metadata.  Returns ``"skip"`` when the segment
        has no enrichment column to rewrite, ``"partial"`` when ``max_rows``
        cut the pass short (progress checkpointed, resumed next pass), and
        ``"done"`` on install.

        Two-phase when a previously-claimed rule's bits are REINTERPRETED
        (pattern changed or rule removed): first a meta-only update
        withdraws those coverage claims — concurrent readers fall back to
        scanning for them — and only then is the new data installed and
        claimed.  A reader therefore never pairs an old claim with new bits
        (or vice versa); pure additions skip the extra phase because no old
        plan can reference a rule the old metadata never claimed.

        Incremental checkpointing: rows are matched in ``[start, stop)``
        passes; an incomplete pass persists ``(target key, row high-water
        mark, partial bitmap)`` next to the spill files and the next pass —
        by this worker or a restarted replacement — resumes from the
        watermark.  Checkpoints stage work OUTSIDE the segment's visible
        artifacts; readers never observe a partially backfilled bitmap.
        ``fence`` threads the lease's fencing token into every
        ``apply_update`` (withdraw and install)."""
        t = self._target
        if ENRICH_COLUMN not in seg.meta["columns"]:
            return "skip"
        delta_ids, removed_ids = self.segment_delta(seg)
        seg_idents = seg.meta.get("rule_idents") or {}
        reinterpreted = ([r for r in delta_ids if str(r) in seg_idents]
                         + removed_ids)
        if reinterpreted and seg.meta.get("rules_known") is not None:
            drop = {str(r) for r in reinterpreted}
            kept = {rid: ident for rid, ident in seg_idents.items()
                    if rid not in drop}
            seg.apply_update(meta_updates={
                "rule_idents": kept,
                "rules_known": pack_known_bitmap(
                    kept, seg.meta["columns"][ENRICH_COLUMN][1][1]),
            }, fence=fence)
            # the withdraw changed coverage; re-derive the delta so the
            # checkpoint key (and resume) see the post-withdraw world
            seg_idents = seg.meta.get("rule_idents") or {}
            delta_ids, removed_ids = self.segment_delta(seg)
        num_rules = t.ruleset.num_rules
        W = max(words_for_rules(max(num_rules, 1)),
                seg.meta["columns"][ENRICH_COLUMN][1][1])
        N = seg.num_records
        ckpt_key = f"{t.version}:{','.join(map(str, delta_ids))}"
        start, done_bm = self._load_checkpoint(seg, ckpt_key)
        if report is not None and start:
            report.rows_resumed += start
        stop = N if max_rows is None else min(N, start + max(int(max_rows), 1))

        def read_rows(name):
            # cache=False: a maintenance pass streams each column range
            # once — it must not pin the whole spilled dataset in RAM.
            # Whole-segment passes (the common case) read the column
            # directly; partial passes page in just the row range.
            if start == 0 and stop == N:
                return np.asarray(seg.column(name, cache=False))
            return np.asarray(seg.column_rows(
                name, np.arange(start, stop), cache=False))

        old = read_rows(ENRICH_COLUMN)
        part = np.zeros((stop - start, W), np.uint32)
        part[:, :old.shape[1]] = old
        # keep exactly the bits whose rule identity already matches the
        # target; everything else (delta, removed, never-claimed strays) is
        # cleared and — for the delta — recomputed below.  Idempotent
        # across the withdraw above and across checkpoint resumes.
        keep = [int(rid) for rid, ident in t.idents.items()
                if seg_idents.get(rid) == ident and int(rid) < W * 32]
        part &= rule_mask(keep, W * 32) if keep else np.uint32(0)
        if delta_ids:
            delta_rules = tuple(r for r in t.ruleset.rules
                                if r.rule_id in set(delta_ids))
            matchers = self._matchers_for(delta_rules, seg)
            for fieldname, engine in matchers.items():
                if fieldname not in seg.meta["columns"]:
                    continue
                sub = np.asarray(engine.match(read_rows(fieldname)))
                part[:, :sub.shape[1]] |= sub
        if report is not None:
            report.rows_matched += stop - start
        bm = part if done_bm is None else np.concatenate([done_bm, part])
        if stop < N:
            self._save_checkpoint(seg, ckpt_key, stop, bm)
            return "partial"
        enrich_meta, postings = derive_enrichment_meta(bm)
        meta_updates = {
            **enrich_meta,
            "rule_idents": dict(t.idents),
            "rules_known": pack_known_bitmap(t.idents, W),
        }
        seg.apply_update(columns={ENRICH_COLUMN: bm},
                         meta_updates=meta_updates, rule_postings=postings,
                         fence=fence)
        self._clear_checkpoint(seg)
        return "done"

    # -- checkpoint plane --------------------------------------------------
    def _save_checkpoint(self, seg, key: str, hwm: int,
                         bm: np.ndarray) -> None:
        """Persist partial progress atomically (tmp + ``os.replace``), next
        to the spill files.  Memory-only segments checkpoint in the worker
        (survives budget cuts within a process, not a restart — but neither
        does the segment)."""
        _BF_CHECKPOINTS.inc()
        telemetry.emit("backfill_checkpoint", plane="maintenance",
                       segment=seg.segment_id, rows_done=int(hwm))
        if seg.path is None:
            self._mem_ckpts[seg.segment_id] = (key, hwm, bm)
            return
        faults.fire("maintenance.checkpoint", segment=seg.segment_id)
        path = seg.path / CKPT_NAME
        tmp = path.with_name(path.name + ".tmp")
        with open(tmp, "wb") as f:
            np.savez_compressed(f, key=np.asarray([key]),
                                hwm=np.asarray([hwm], np.int64), bm=bm)
        os.replace(tmp, path)

    def _load_checkpoint(self, seg, key: str) -> tuple:
        """-> (resume row, completed-prefix bitmap) — ``(0, None)`` when no
        checkpoint matches the current target key (a moved target, or a
        torn/corrupt file, restarts the segment from row 0)."""
        if seg.path is None:
            mem = self._mem_ckpts.get(seg.segment_id)
            if mem is not None and mem[0] == key:
                return mem[1], mem[2]
            return 0, None
        path = seg.path / CKPT_NAME
        if not path.exists():
            return 0, None
        try:
            with np.load(path, allow_pickle=False) as z:
                if str(z["key"][0]) == key:
                    return int(z["hwm"][0]), np.asarray(z["bm"])
        except (OSError, ValueError, KeyError, EOFError,
                zipfile.BadZipFile) as e:   # torn checkpoint == no checkpoint
            telemetry.suppressed("maintenance.load_checkpoint", e)
        return 0, None

    def _clear_checkpoint(self, seg) -> None:
        self._mem_ckpts.pop(seg.segment_id, None)
        if seg.path is not None:
            try:
                # most segments finish in one pass and never checkpoint
                (seg.path / CKPT_NAME).unlink(missing_ok=True)
            except OSError as e:
                telemetry.suppressed("maintenance.clear_checkpoint", e)

    def warm_matchers(self, candidates: list = None) -> int:
        """Precompile the delta matchers the current target needs over this
        worker's pending segments.  Returns how many engines were compiled
        (0 when the cache was already warm — the idempotent steady state).
        Called automatically once per installed target version at the top
        of the first cycle; safe to call explicitly (a process-pool worker
        warms right after opening the store, before its first timed
        cycle)."""
        if self._target is None:
            return 0
        t = self._target
        if candidates is None:
            candidates = self._refresh_pending()
        compiled = 0
        for seg in candidates:
            delta_ids, _removed = self.segment_delta(seg)
            if not delta_ids:
                continue
            delta_rules = tuple(r for r in t.ruleset.rules
                                if r.rule_id in set(delta_ids))
            if self._matcher_key(delta_rules, seg) not in self._matchers:
                self._matchers_for(delta_rules, seg)
                compiled += 1
        self._warmed_version = t.version
        if compiled:
            telemetry.emit("matcher_cache_warmed", plane="maintenance",
                           worker=self.worker_id, version=t.version,
                           compiled=compiled)
        return compiled

    def _matcher_key(self, delta_rules: tuple, seg) -> tuple:
        fields = tuple(sorted(
            name for name, (dtype, shape) in seg.meta["columns"].items()
            if dtype == "uint8" and len(shape) == 2))
        return (self._target.version,
                tuple(r.rule_id for r in delta_rules), fields)

    def _matchers_for(self, delta_rules: tuple, seg) -> dict:
        """Compile (and cache) matchers for a delta sub-ruleset, keeping the
        ORIGINAL rule ids so emitted bitmaps OR straight into the segment's
        bitmap words."""
        key = self._matcher_key(delta_rules, seg)
        if key not in self._matchers:
            bundle = compile_bundle(RuleSet(delta_rules),
                                    key[2])     # the matchable fields
            self._matchers[key] = build_matchers(
                bundle, backend=self.backend, block_n=self.block_n)
        return self._matchers[key]
