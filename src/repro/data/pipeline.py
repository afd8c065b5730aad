"""Streaming pipelines wiring the FluxSieve stream processor into (a) the
analytical plane and (b) LM training — DESIGN.md §3.

``IngestPipeline`` is the paper's deployment: source -> StreamProcessor
(match + enrich) -> SegmentStore, with per-stage throughput/CPU accounting
(benchmarks read these for the Fig-5 overhead analysis).

``TrainDataPipeline`` is the framework integration: the same enriched
stream feeds LM training; rule bitmaps ride along each batch so trainers
can subselect (``include_rules`` / ``exclude_rules``) without rescanning
bytes — ingest-time data curation (quality/PII filters) as a first-class
data-plane feature.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from repro.core import enrichment, faults, telemetry
from repro.core.faults import InjectedCrash
from repro.core.records import RecordBatch
from repro.core.stream_processor import (ENRICH_COLUMN, BatchMatchError,
                                         StreamProcessor)
from repro.core.query.store import INGEST_WAL_DIRNAME as WAL_DIRNAME
from repro.core.query.store import SegmentStore
from repro.data import tokenizer
from repro.data.generator import LogGenerator

QUARANTINE_DIRNAME = "quarantine"   # dead-letter home for unmatched batches

# per-batch stage latencies (one observe per batch, not per record) plus
# throughput/overlap counters — the snapshot-side view of StageTimes
_STAGE_HIST = {
    stage: telemetry.histogram(
        "fluxsieve_ingest_stage_seconds", labels={"stage": stage},
        help="Per-batch host seconds by ingest stage.")
    for stage in ("generate", "wal", "dispatch", "finalize_wait", "store")
}
_INGEST_RECORDS = telemetry.counter(
    "fluxsieve_ingest_records_total",
    help="Records ingested through the pipeline.")
_INGEST_BATCHES = telemetry.counter(
    "fluxsieve_ingest_batches_total",
    help="Batches pushed through the ingest loop.")
_OVERLAP_S = telemetry.counter(
    "fluxsieve_ingest_overlap_seconds_total",
    help="Host seconds spent generating/storing while a dispatched match "
         "was still in flight (double-buffering overlap).")
_WAL_WRITES = telemetry.counter(
    "fluxsieve_wal_writes_total",
    help="Batches journaled to the ingest WAL.")
_WAL_REPLAYED = telemetry.counter(
    "fluxsieve_wal_replayed_records_total",
    help="Records re-ingested from the WAL during crash recovery.")
_QUARANTINED = telemetry.counter(
    "fluxsieve_ingest_quarantined_total",
    help="Records dead-lettered after failing both match lanes.")


def _atomic_save_batch(path: Path, columns: dict) -> None:
    """Batch container: a name list then one raw ``np.save`` per column,
    concatenated in one file — ~4x cheaper than npz on the hot journal
    path (the zip container CRCs every member).  Written via tmp +
    ``os.replace``, the same all-or-nothing discipline as the manifest and
    the backfill checkpoint: a reader never observes a torn entry, a
    crashed writer leaves only a ``.tmp``."""
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "wb") as f:
        np.save(f, np.array(list(columns)))
        for v in columns.values():
            np.save(f, v)
    os.replace(tmp, path)


def _load_batch(path: Path) -> RecordBatch:
    with open(path, "rb") as f:
        names = np.load(f)
        return RecordBatch(columns={str(nm): np.load(f) for nm in names})


class IngestWAL:
    """Per-batch write-ahead journal for crash-safe ingest.

    The double-buffered ingest loop holds up to two batches of volatile
    state (batch *k* dispatched, batch *k-1* appending) and the store
    buffers rows in memory until a seal — so a kill can lose up to a
    segment's worth of source rows.  The WAL closes that window: each raw
    (pre-enrichment) batch is journaled *before* dispatch, and recovery
    replays every journaled row past the store's durability watermark.

    Exactly-once hinges on one invariant, owned by the store: the manifest
    ``sealed_rows`` watermark advances in the SAME atomic commit that
    registers a sealed segment.  Entry files are named
    ``batch-<row_start>-<nrows>.npy`` in *source-row* coordinates, so

      * ``truncate(W)`` deletes entries fully below the watermark,
      * ``replay(W)`` yields rows from exactly W (slicing the straddling
        entry), never re-ingesting a sealed row and never skipping an
        unsealed one.

    Requires enrich mode: the watermark counts source rows, which filter
    mode does not preserve through the store."""

    def __init__(self, root):
        self.dir = Path(root) / WAL_DIRNAME
        self.dir.mkdir(parents=True, exist_ok=True)

    def _path(self, row_start: int, n: int) -> Path:
        return self.dir / f"batch-{row_start:012d}-{n:08d}.npy"

    def append(self, row_start: int, batch: RecordBatch) -> None:
        faults.fire("ingest.wal_append", row_start=int(row_start))
        _atomic_save_batch(self._path(row_start, len(batch)), batch.columns)
        _WAL_WRITES.inc()

    def entries(self) -> list:
        """Sorted [(row_start, nrows, path)] of intact journal entries."""
        out = []
        for p in sorted(self.dir.glob("batch-*.npy")):
            try:
                _, start, n = p.stem.split("-")
                out.append((int(start), int(n), p))
            except ValueError:
                continue
        return out

    def truncate(self, durable_rows: int) -> None:
        """Reclaim entries whose rows are all durable (below the manifest
        watermark — sealed or quarantined)."""
        for row_start, n, p in self.entries():
            if row_start + n <= durable_rows:
                try:
                    p.unlink()
                except OSError as e:
                    telemetry.suppressed("ingest.wal_truncate", e)

    def replay(self, watermark: int):
        """Yield ``(row_start, RecordBatch)`` for journaled rows at or past
        the durability watermark, slicing the straddling entry so replay
        starts at exactly row ``watermark``."""
        for row_start, n, p in self.entries():
            if row_start + n <= watermark:
                continue
            batch = _load_batch(p)
            if row_start < watermark:
                batch = batch.slice(watermark - row_start, n)
                row_start = watermark
            yield row_start, batch

    def end(self) -> int:
        """Highest journaled source row (resume point for the source)."""
        entries = self.entries()
        if not entries:
            return 0
        row_start, n, _ = entries[-1]
        return row_start + n


@dataclass
class StageTimes:
    """Per-stage host time.  With the pipelined (double-buffered) loop,
    ``process_s`` is dispatch time plus the time *blocked* waiting for a
    result; device compute hidden behind generate/store shows up in
    ``overlap_s`` instead (host seconds spent generating or storing while a
    dispatched match was still in flight), so the stage sum stays an honest
    account of where the wall clock went."""
    generate_s: float = 0.0
    wal_s: float = 0.0
    process_s: float = 0.0
    store_s: float = 0.0
    overlap_s: float = 0.0
    records: int = 0
    cpu_s: float = 0.0
    wall_s: float = 0.0

    def throughput(self) -> float:
        total = self.generate_s + self.wal_s + self.process_s + self.store_s
        return self.records / total if total else 0.0

    def sustained_rate(self) -> float:
        return self.records / self.wall_s if self.wall_s else 0.0

    def cpu_busy_fraction(self) -> float:
        return self.cpu_s / self.wall_s if self.wall_s else 0.0


class IngestPipeline:
    """generator -> [stream processor] -> segment store.

    ``processor=None`` is the paper's *baseline* lane (decode + write only);
    with a processor it is the FluxSieve lane (match + enrich + write).
    The FluxSieve lane is double-buffered: JAX's async dispatch lets the
    device match batch *k* while the host appends batch *k-1* to the
    SegmentStore — the bitmap stays a device array until the append-side
    ``finalize`` materializes it (one D2H per batch).

    ``wal=True`` (rooted stores, enrich mode only) journals every raw
    batch before dispatch and truncates against the store's manifest
    watermark; after a kill, ``recover()`` replays the journal so every
    source row lands in a sealed segment exactly once.  Batches that fail
    BOTH match lanes (primary + oracle fallback) are dead-lettered to
    ``<root>/quarantine/`` and skipped — the stream keeps flowing."""

    def __init__(self, generator: LogGenerator, store: SegmentStore,
                 processor: StreamProcessor = None, *, wal: bool = False):
        self.generator = generator
        self.store = store
        self.processor = processor
        if processor is not None and store.version_rules is None:
            # share the processor's live version->rules registry so seals
            # stamp rule-aware coverage metadata (``rules_known``) that the
            # mapper and the maintenance plane consume
            store.version_rules = processor.version_rules
        self.wal = None
        if wal:
            if store.root is None:
                raise ValueError("the ingest WAL needs a rooted store "
                                 "(it lives next to the spill dirs)")
            if processor is not None and processor.mode == "filter":
                raise ValueError(
                    "the ingest WAL requires enrich mode: its durability "
                    "watermark counts source rows, which filter mode does "
                    "not preserve through the store")
            self.wal = IngestWAL(store.root)
        self.quarantined = 0
        self.times = StageTimes()

    def _flush(self, pending, row_start: int) -> tuple:
        """finalize + append one pending batch; -> (wait_s, store_s).
        A finalize failure (e.g. the D2H transfer) gets ONE synchronous
        re-run of the whole batch; a second failure dead-letters it."""
        t0 = time.perf_counter()
        with telemetry.span("ingest/finalize_wait", cat="ingest"):
            try:
                out = self.processor.finalize(pending)
            except InjectedCrash:
                raise
            except Exception as e:  # noqa: BLE001 — degrade, not crash
                out = self._refinalize(pending, row_start, e)
        t1 = time.perf_counter()
        with telemetry.span("ingest/store", cat="ingest"):
            if out is not None:
                faults.fire("ingest.append", n=len(out))
                self.store.append(out)
                if self.wal is not None:
                    with telemetry.span("ingest/wal_truncate", cat="ingest"):
                        self.wal.truncate(self.store.sealed_rows)
        t2 = time.perf_counter()
        _STAGE_HIST["finalize_wait"].observe(t1 - t0)
        _STAGE_HIST["store"].observe(t2 - t1)
        return t1 - t0, t2 - t1

    def _refinalize(self, pending, row_start: int, err):
        """Finalize failed: one fresh synchronous pass (re-dispatch + D2H),
        then quarantine.  Returns the enriched batch or None (dead-lettered)."""
        try:
            return self.processor.process(pending.batch)
        except InjectedCrash:
            raise
        except Exception as e:  # noqa: BLE001
            self._quarantine(row_start, pending.batch, e)
            return None

    def _quarantine(self, row_start: int, batch: RecordBatch, err) -> None:
        """Dead-letter a batch that no match lane could process: spill the
        raw rows to ``<root>/quarantine/`` and advance the durability
        watermark past them (they are durable — just not queryable), so
        the WAL truncates and recovery never replays them as lost."""
        if self.store.root is None:
            raise err   # no durable dead-letter home: fail loudly
        qdir = Path(self.store.root) / QUARANTINE_DIRNAME
        qdir.mkdir(parents=True, exist_ok=True)
        _atomic_save_batch(qdir / f"batch-{row_start:012d}-{len(batch):08d}.npy",
                      batch.columns)
        self.store.account_skipped_rows(len(batch))
        if self.wal is not None:
            self.wal.truncate(self.store.sealed_rows)
        self.quarantined += len(batch)
        _QUARANTINED.inc(len(batch))
        telemetry.emit("quarantine", plane="ingest", row_start=int(row_start),
                       records=len(batch),
                       error=f"{type(err).__name__}: {err}")

    def recover(self) -> int:
        """Replay journaled batches past the store's durability watermark
        (call on a freshly ``SegmentStore.load``-ed store after a crash).
        Replayed rows are re-enriched and sealed immediately — after this
        returns, everything journaled is durable.  Returns the source row
        ingest should resume from (pass as ``run(start=...)``)."""
        if self.wal is None:
            return self.store.sealed_rows
        watermark = self.store.sealed_rows
        resume = max(watermark, self.wal.end())
        replayed = 0
        with telemetry.span("ingest/wal_replay", cat="ingest"):
            for row_start, batch in self.wal.replay(watermark):
                if self.processor is not None:
                    try:
                        batch = self.processor.process(batch)
                    except InjectedCrash:
                        raise
                    except BatchMatchError as e:
                        self._quarantine(row_start, batch, e)
                        continue
                faults.fire("ingest.append", n=len(batch))
                self.store.append(batch)
                replayed += len(batch)
        if replayed:
            self.store.seal()
            _WAL_REPLAYED.inc(replayed)
            telemetry.emit("wal_replay", plane="ingest", records=replayed,
                           watermark=int(watermark), resume=int(resume))
        self.wal.truncate(self.store.sealed_rows)
        return resume

    def run(self, *, batch_size: int = 4096, limit: int = None,
            poll_updates: bool = True, target_rate: float = None,
            pipelined: bool = True, start: int = 0) -> StageTimes:
        """``target_rate`` (records/s) paces the source like the paper's
        fixed-rate Kafka input (Fig 5: 10k events/s); without it the
        pipeline runs saturated.  ``pipelined=False`` forces the strictly
        sequential generate->match->store loop (A/B accounting).
        ``start`` resumes the source mid-stream — crash recovery passes
        ``recover()``'s return value here."""
        t = self.times
        cpu0 = time.process_time()
        wall0 = time.perf_counter()
        total = limit or self.generator.spec.num_records
        done0 = start               # source rows ingested before this run
        pending = None              # batch k-1, dispatched but not stored
        pending_start = 0           # its source row (WAL/quarantine coords)
        while start < total:
            n = min(batch_size, total - start)
            t0 = time.perf_counter()
            with telemetry.span("ingest/generate", cat="ingest", n=n):
                batch = self.generator.batch(start, n)
            t1 = time.perf_counter()
            t.generate_s += t1 - t0
            _STAGE_HIST["generate"].observe(t1 - t0)
            # only device-side results can actually be in flight; host
            # backends (dfa_selective) matched synchronously at dispatch
            if pending is not None and pending.result.on_device:
                t.overlap_s += t1 - t0          # generated while k-1 matched
                _OVERLAP_S.inc(t1 - t0)
            if self.wal is not None:
                # journal FIRST: once the entry lands, a kill anywhere in
                # the dispatch/flush machinery below cannot lose the batch
                with telemetry.span("ingest/wal", cat="ingest", n=n):
                    self.wal.append(start, batch)
                wal_s = time.perf_counter() - t1
                t.wal_s += wal_s
                _STAGE_HIST["wal"].observe(wal_s)
            if self.processor is None:
                ts = time.perf_counter()
                with telemetry.span("ingest/store", cat="ingest"):
                    faults.fire("ingest.append", n=n)
                    self.store.append(batch)
                    if self.wal is not None:
                        with telemetry.span("ingest/wal_truncate",
                                            cat="ingest"):
                            self.wal.truncate(self.store.sealed_rows)
                store_s = time.perf_counter() - ts
                t.store_s += store_s
                _STAGE_HIST["store"].observe(store_s)
            else:
                td = time.perf_counter()
                if poll_updates:
                    self.processor.poll_updates()  # control topology
                with telemetry.span("ingest/dispatch", cat="ingest", n=n):
                    try:
                        pb = self.processor.process_async(batch)
                    except BatchMatchError as e:
                        # both lanes failed: drain k-1 first (its rows
                        # precede this batch — the watermark is a prefix),
                        # then dead-letter and keep the stream flowing
                        if pending is not None:
                            self._flush(pending, pending_start)
                            pending = None
                        self._quarantine(start, batch, e)
                        pb = None
                dispatch_s = time.perf_counter() - td
                t.process_s += dispatch_s
                _STAGE_HIST["dispatch"].observe(dispatch_s)
                if pb is None:
                    pass                        # quarantined above
                elif pipelined:
                    if pending is not None:
                        wait_s, store_s = self._flush(pending, pending_start)
                        t.process_s += wait_s
                        t.store_s += store_s
                        if pb.result.on_device:
                            t.overlap_s += store_s  # stored k-1, k in flight
                            _OVERLAP_S.inc(store_s)
                    pending, pending_start = pb, start
                else:
                    wait_s, store_s = self._flush(pb, start)
                    t.process_s += wait_s
                    t.store_s += store_s
            t.records += n
            _INGEST_RECORDS.inc(n)
            _INGEST_BATCHES.inc()
            start += n
            if target_rate:
                ahead = ((start - done0) / target_rate
                         - (time.perf_counter() - wall0))
                if ahead > 0:
                    time.sleep(ahead)
        if pending is not None:
            wait_s, store_s = self._flush(pending, pending_start)
            t.process_s += wait_s
            t.store_s += store_s
        self.store.seal()
        if self.wal is not None:
            self.wal.truncate(self.store.sealed_rows)
        t.cpu_s = time.process_time() - cpu0
        t.wall_s = time.perf_counter() - wall0
        return t


class TrainDataPipeline:
    """Enriched log stream -> packed LM token batches.

    Rule bitmaps ride along; ``include_rules``/``exclude_rules`` subselect
    records by precomputed enrichment before tokenization (no byte rescans).
    """

    def __init__(self, generator: LogGenerator,
                 processor: StreamProcessor = None, *,
                 include_rules=None, exclude_rules=None):
        self.generator = generator
        self.processor = processor
        self.include_rules = tuple(include_rules or ())
        self.exclude_rules = tuple(exclude_rules or ())
        if (self.include_rules or self.exclude_rules) and processor is None:
            raise ValueError("rule-based selection needs a stream processor")

    def _select(self, batch: RecordBatch) -> RecordBatch:
        if not (self.include_rules or self.exclude_rules):
            return batch
        bm = batch.columns[ENRICH_COLUMN]
        n_rules = self.processor.num_rules
        keep = np.ones(len(batch), bool)
        if self.include_rules:
            mask = enrichment.rule_mask(self.include_rules, n_rules)
            keep &= (bm & mask[None]).any(axis=1)
        if self.exclude_rules:
            mask = enrichment.rule_mask(self.exclude_rules, n_rules)
            keep &= ~(bm & mask[None]).any(axis=1)
        return batch.select(keep)

    def batches(self, *, seq_len: int, batch_size: int,
                records_per_step: int = 2048, limit_steps: int = None):
        """Yield {'tokens': (B, S), 'labels': (B, S)} train batches."""
        start = 0
        step = 0
        spec = self.generator.spec
        while limit_steps is None or step < limit_steps:
            raw = self.generator.batch(start % spec.num_records,
                                       records_per_step)
            start += records_per_step
            if self.processor is not None:
                self.processor.poll_updates()
                raw = self.processor.process(raw)
            raw = self._select(raw)
            if len(raw) == 0:
                continue
            text = np.concatenate([raw.columns[f] for f in raw.text_fields],
                                  axis=1)
            rows = tokenizer.encode_bytes(text)
            tokens, labels = tokenizer.pack_sequences(rows, seq_len, batch_size)
            yield {"tokens": tokens, "labels": labels}
            step += 1
