"""Columnar analytical plane — segments, seal, spill, zone maps, FTS index.

The in-framework analogue of Pinot REALTIME segments / a Parquet data lake:
record batches append into an active (mutable) segment; at ``segment_size``
records the segment **seals** — columns freeze, per-segment metadata (zone
maps) is derived, and an optional **text index** (token -> posting list, the
Pinot FTS analogue) is built.  Sealed segments can **spill** to disk as one
file per column, so queries read only the columns they touch (columnar I/O),
and caches can be dropped per column to measure genuine cold-run behaviour
(paper §4.2).

Zone maps kept per segment:
  * min/max ``timestamp``;
  * OR of all enrichment bitmaps (``rule_bitmap_any``) — a segment whose
    combined bitmap lacks a query's rule bits is **pruned without any I/O**,
    the mechanism behind the paper's cold-run wins ("data pruning possible
    with our approach that avoids I/O bottlenecks", §6.3.1);
  * min/max ``engine_version_id`` — consistency propagation (§3.4 step 4):
    the mapper only uses the enriched path on segments whose records were all
    ingested with an engine that knew the rule.

Durability invariants (maintenance plane v2):
  * **meta-flips-last** — ``Segment.apply_update`` installs data before
    flipping ``meta`` and bumps the cache token after, so no stale derived
    state can ever be cached under a live token;
  * **manifest is the commit point** — segment-set membership (seal
    registration, compaction swap, retention retire) changes as ONE atomic
    :class:`Manifest` write; ``SegmentStore.load`` trusts it, closing the
    crash window where a merged segment and its un-retired inputs coexist
    on disk (RETIRED tombstones are advisory: legacy loads + GC keys);
  * **fenced writes** — ``apply_update(fence=...)`` runs the maintenance
    plane's epoch-fencing barrier inside the write lock, before the first
    mutation (see ``repro.core.maintenance.lease``).
"""
from __future__ import annotations

import json
import os
import re
import threading
import warnings
import weakref
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from repro.core import faults, telemetry
from repro.core.records import RecordBatch, decode_texts
from repro.core.stream_processor import ENGINE_VERSION_COLUMN, ENRICH_COLUMN

_SEALED = telemetry.counter(
    "fluxsieve_store_segments_sealed_total",
    help="Segments sealed out of the active append buffer.")
_COMMITS = telemetry.counter(
    "fluxsieve_store_manifest_commits_total",
    help="Atomic root-manifest commits.")
_EPOCH_PUBLISHES = telemetry.counter(
    "fluxsieve_store_epoch_publishes_total",
    help="Maintenance epochs published to subscribers.")
_SEGMENTS_MISSING = telemetry.counter(
    "fluxsieve_store_segments_missing_total",
    help="Manifest-listed spill dirs found missing at load (data loss).")

_TOKEN_RE = re.compile(r"[A-Za-z0-9_\-./:]+")

# fraction of a segment above which a rule is "dense" and gets no posting list
POSTING_DENSITY_CUT = 0.1

# tombstone file marking a spill dir replaced by compaction/retention: load()
# skips it (pre-manifest stores), SpillGC deletes it once no reader remains
RETIRED_MARKER = "RETIRED"

# root manifest: the authoritative valid-segment set + fencing-epoch registry
MANIFEST_NAME = "manifest.json"

# the ingest WAL's home under the store root (owned by data/pipeline, named
# here so load() can recognize a WAL-born store without a circular import)
INGEST_WAL_DIRNAME = "ingest-wal"

# meta key stamped by the retention plane (maintenance.retention) on
# segments straddling the TTL horizon: rows with timestamp < this value are
# logically expired.  The planner filters them at plan time (immediate
# query invisibility); the Compactor's next rewrite drops them physically.
RETENTION_CUTOFF = "retention_cutoff"

# epoch change kinds published to subscribe_epochs listeners
EPOCH_KINDS = ("seal", "update", "drop", "replace", "retire")


@dataclass(frozen=True)
class EpochDelta:
    """One maintenance epoch's change record — the payload of the
    ``subscribe_epochs`` feed (the richer sibling of the legacy
    ``subscribe_maintenance`` segment-id feed).

    ``kind`` names the change class:

      ``seal``     a new segment entered the store off the append path;
      ``update``   ``Segment.apply_update`` swapped enrichment artifacts
                   (backfill install, retention-cutoff stamp);
      ``drop``     a cold-run cache drop bumped tokens (data unchanged —
                   derived device/host caches are invalid, results are not);
      ``replace``  compaction swapped ``segment_ids`` out for ``added``;
      ``retire``   retention removed ``segment_ids`` with no replacement.

    ``segment_ids`` are the ids whose previous state this epoch
    invalidates (for ``seal`` the new segment's own id); ``added`` carries
    the Segment objects entering the store (seal/replace); ``tokens`` maps
    every affected id still in the store to its post-change
    ``meta_token()`` — the affected-version detail incremental consumers
    (standing queries) compare against their folded state, so a duplicated
    delivery or an already-folded epoch is recognized without re-reading
    any data."""
    seq: int
    kind: str
    segment_ids: tuple
    added: tuple = ()
    tokens: dict = field(default_factory=dict)


def tokenize(text: str) -> list:
    return _TOKEN_RE.findall(text)


class Manifest:
    """Crash-safe root manifest for a spilled ``SegmentStore``.

    A hard kill between a compactor spilling its merged segment and
    tombstoning the inputs used to leave BOTH on disk, so a later
    ``SegmentStore.load`` would double-count every merged record.  The
    manifest closes that window by making segment-set membership a single
    atomic commit: the valid segment set (plus the id allocator's
    high-water mark and the maintenance plane's fencing epochs) lives in
    one small JSON document, rewritten via tmp + ``os.replace`` — a reload
    sees either the pre-swap or the post-swap world, never a mix.

    Commit protocol (writers):
      * a sealed segment spills FIRST, then registers — a crash in between
        leaves an unregistered dir that ``load`` ignores;
      * compaction materializes its merged segment *unregistered*
        (``make_segment_from_batch``), and ``replace_segments`` commits
        "new in, old out" as ONE manifest write — the commit point; the
        RETIRED tombstones written afterwards are advisory (for
        pre-manifest readers and the GC), not load-bearing;
      * lease epochs persist here too (``fences``), so a restarted process
        can never re-issue a fencing token an earlier holder already wrote
        under (see ``maintenance.lease.LeaseManager``).

    Thread-safe; state is held in memory and every ``commit`` rewrites the
    full (small) document atomically.
    """

    def __init__(self, root):
        self.root = Path(root)
        self.path = self.root / MANIFEST_NAME
        self._lock = threading.Lock()
        self._state = {"segments": {}, "next_id": 0, "fences": {},
                       "sealed_rows": 0}

    @staticmethod
    def read(root) -> dict:
        """The on-disk manifest state, or None when no manifest exists
        (pre-manifest store — ``load`` falls back to directory scanning)."""
        path = Path(root) / MANIFEST_NAME
        if not path.exists():
            return None
        return json.loads(path.read_text())

    def adopt(self, state: dict) -> None:
        """Install previously persisted state (``SegmentStore.load``)."""
        with self._lock:
            self._state = {"segments": dict(state.get("segments", {})),
                           "next_id": int(state.get("next_id", 0)),
                           "fences": dict(state.get("fences", {})),
                           "sealed_rows": int(state.get("sealed_rows", 0))}

    def commit(self, *, add: dict = None, remove=None, next_id: int = None,
               fences: dict = None, sealed_rows: int = None) -> None:
        """Atomically apply a membership/epoch delta and persist.

        ``add``: {segment_id: dirname}; ``remove``: segment ids;
        ``next_id``: id-allocator high-water mark (monotonic);
        ``fences``: {segment_id: epoch} (monotonic per segment);
        ``sealed_rows``: ingest durability watermark — total rows the
        ingest path has sealed into registered segments (monotonic; the
        WAL truncates, and crash recovery dedups, against it)."""
        faults.fire("store.manifest_commit", root=str(self.root))
        with self._lock:
            seg = self._state["segments"]
            if add:
                for sid, name in add.items():
                    seg[str(int(sid))] = str(name)
            for sid in (remove or ()):
                seg.pop(str(int(sid)), None)
            if next_id is not None:
                self._state["next_id"] = max(self._state["next_id"],
                                             int(next_id))
            if fences:
                f = self._state["fences"]
                for sid, epoch in fences.items():
                    key = str(int(sid))
                    f[key] = max(int(f.get(key, 0)), int(epoch))
            if sealed_rows is not None:
                self._state["sealed_rows"] = max(
                    self._state.get("sealed_rows", 0), int(sealed_rows))
            _atomic_write_text(self.path,
                               json.dumps(self._state, sort_keys=True))
        _COMMITS.inc()
        telemetry.emit("manifest_commit", plane="store",
                       added=len(add or ()), removed=len(tuple(remove or ())),
                       fenced=len(fences or ()))

    # -- readers -----------------------------------------------------------
    def segment_dirs(self) -> list:
        """Valid spill dirs in segment-id order (the load set)."""
        with self._lock:
            items = sorted(self._state["segments"].items(),
                           key=lambda kv: int(kv[0]))
            return [self.root / name for _, name in items]

    def segment_ids(self) -> set:
        with self._lock:
            return {int(s) for s in self._state["segments"]}

    def next_id(self) -> int:
        with self._lock:
            return self._state["next_id"]

    def fences(self) -> dict:
        with self._lock:
            return {int(s): int(e)
                    for s, e in self._state["fences"].items()}

    def sealed_rows(self) -> int:
        """Ingest durability watermark: rows sealed into registered
        segments (crash recovery replays WAL entries past it)."""
        with self._lock:
            return int(self._state.get("sealed_rows", 0))


def build_text_index(data: np.ndarray) -> dict:
    """(N, L) uint8 -> token -> sorted int32 record ids (inverted index)."""
    postings: dict = {}
    for rid, text in enumerate(decode_texts(data)):
        for tok in set(tokenize(text)):
            postings.setdefault(tok, []).append(rid)
    return {t: np.asarray(ids, np.int32) for t, ids in postings.items()}


def derive_enrichment_meta(bm: np.ndarray) -> tuple:
    """(N, W) uint32 rule bitmap -> (meta_updates, rule_postings).

    Shared by seal, backfill, and compaction so every producer of an
    enrichment column derives identical zone maps / counts / postings:
      * ``rule_bitmap_any``  — OR of all bitmaps (zone-map pruning);
      * ``rule_counts``      — per-rule match counts (metadata-only counts);
      * posting lists for selective rules (the bitmap's inverted index).
    """
    # byte views need C order; a TPU may hand bitmaps back in F order
    bm = np.ascontiguousarray(bm)
    bm_any = np.bitwise_or.reduce(bm, axis=0) if len(bm) else \
        np.zeros(bm.shape[1], np.uint32)
    meta = {"rule_bitmap_any": bm_any.tolist()}
    bits = np.unpackbits(bm.view(np.uint8), axis=1, bitorder="little")
    counts = bits.sum(axis=0)
    meta["rule_counts"] = [[int(r), int(c)]
                           for r, c in enumerate(counts) if c]
    postings = {}
    dense_cut = max(1, int(POSTING_DENSITY_CUT * len(bm)))
    for r, c in meta["rule_counts"]:
        if c <= dense_cut:
            postings[str(r)] = np.flatnonzero(bits[:, r]).astype(np.int32)
    return meta, postings


def rules_known_for_versions(version_rules: dict, version_ids) -> dict:
    """Intersect the rule-ident maps of every engine version present in a
    segment: str(rule_id) -> ident for rules that ALL versions knew with the
    same content identity.  A version missing from the registry contributes
    nothing (safe: those rules fall back to scanning)."""
    maps = [version_rules.get(int(v)) for v in version_ids]
    if not maps or any(m is None for m in maps):
        return {}
    common = dict(maps[0])
    for m in maps[1:]:
        common = {rid: ident for rid, ident in common.items()
                  if m.get(rid) == ident}
    return common


def pack_known_bitmap(idents: dict, words: int) -> list:
    """{str(rule_id): ident} -> packed uint32 words (list, JSON-able)."""
    known = np.zeros(words, np.uint32)
    for rid in idents:
        r = int(rid)
        if r < words * 32:
            known[r // 32] |= np.uint32(1 << (r % 32))
    return known.tolist()


@dataclass
class Segment:
    segment_id: int
    num_records: int
    meta: dict                      # zone maps + schema
    _columns: dict = field(default_factory=dict)     # name -> array (may be empty when spilled)
    _text_index: dict = field(default_factory=dict)  # field -> {token: ids}
    _rule_postings: dict = None     # str(rule_id) -> int32 ids (None = absent)
    _rule_counts: tuple = None      # (source object, {int id: count}) cache
    _meta_gen: int = 0              # bumped on every enrichment swap / cache
                                    # drop; see meta_token()
    path: Path = None               # spill directory (None = memory only)
    # serializes cold-load cache fills against apply_update: without it a
    # reader could np.load the OLD file, get descheduled across a swap, and
    # install the stale array under the NEW metadata — permanently.  The
    # in-cache fast paths stay lock-free (install happens-before meta flip).
    _io_lock: object = field(default_factory=threading.Lock)
    # maintenance-epoch publication hook (set by the owning SegmentStore):
    # called with (segment_ids, kind, changed_segments) AFTER a swap/
    # cache-drop bumps the token, so shared-arrangement readers retire the
    # old epoch instead of racing a cache invalidation, and standing-query
    # folds learn the change kind + post-change tokens
    _on_swap: object = None

    # -- column access ---------------------------------------------------
    @property
    def column_names(self) -> tuple:
        return tuple(self.meta["columns"])

    def meta_token(self) -> tuple:
        """Identity of this segment's current enrichment state, usable as a
        cache key by holders of derived artifacts (the query executor's
        device-resident column cache keys on it).  ``apply_update`` and
        ``drop_caches`` both bump the generation, so a maintenance swap or a
        cold-run cache drop can never serve a stale derived array: the old
        token simply stops being produced.  Segment ids are monotonic and
        never reused (compaction allocates fresh ids), so tokens are unique
        across segment objects of one store."""
        return (self.segment_id, self._meta_gen)

    def column(self, name: str, *, cache: bool = True) -> np.ndarray:
        """Read one column; ``cache=False`` models a cold read (load from
        disk, do not retain)."""
        if name in self._columns:
            return self._columns[name]
        if self.path is None:
            raise KeyError(f"segment {self.segment_id}: column {name} dropped "
                           "with no spill path")
        with self._io_lock:
            if name in self._columns:
                return self._columns[name]
            arr = np.load(self.path / f"{name}.npy")
            if cache:
                self._columns[name] = arr
        return arr

    def column_rows(self, name: str, ids: np.ndarray,
                    *, cache: bool = True) -> np.ndarray:
        """Read only the given rows of a column.  Cold reads memory-map the
        file and touch just the matching pages (row-group reads) instead of
        loading the whole column."""
        if name in self._columns:
            return self._columns[name][ids]
        if self.path is None:
            raise KeyError(f"segment {self.segment_id}: column {name}")
        with self._io_lock:
            if name in self._columns:
                return self._columns[name][ids]
            arr = np.load(self.path / f"{name}.npy", mmap_mode="r")
            out = np.array(arr[ids])
            if cache:  # hot mode retains the full column for later queries
                self._columns[name] = np.array(arr)
        return out

    def text_index(self, fieldname: str, *, cache: bool = True) -> dict:
        if fieldname in self._text_index:
            return self._text_index[fieldname]
        if self.path is None:
            raise KeyError(f"segment {self.segment_id}: no text index for "
                           f"{fieldname}")
        with self._io_lock:
            if fieldname in self._text_index:
                return self._text_index[fieldname]
            idx = _load_index(self.path / f"{fieldname}.fts.npz")
            if cache:
                self._text_index[fieldname] = idx
        return idx

    def has_text_index(self, fieldname: str) -> bool:
        if fieldname in self._text_index:
            return True
        return (self.path is not None
                and (self.path / f"{fieldname}.fts.npz").exists())

    def rule_postings(self, rule_id: int, *, cache: bool = True):
        """Seal-time inverted index over the enrichment column: int32 ids
        for selective rules.  Returns None when unavailable (dense rule or
        segment without enrichment) — callers fall back to the bitmap."""
        if self._rule_postings is None:
            if self.path is None or not (self.path / "rule_postings.npz").exists():
                return None
            with self._io_lock:
                if self._rule_postings is not None:
                    return self._rule_postings.get(str(rule_id))
                idx = _load_index(self.path / "rule_postings.npz")
                if cache:
                    self._rule_postings = idx
            return idx.get(str(rule_id))
        return self._rule_postings.get(str(rule_id))

    def rule_count(self, rule_id: int, meta: dict = None):
        """Per-segment precomputed match count (None when unavailable).
        ``meta`` reads from a caller-held snapshot of ``self.meta``."""
        rc = (self.meta if meta is None else meta).get("rule_counts")
        if rc is None:
            return None
        # normalized lookup lives OUTSIDE meta (meta must stay JSON-shaped:
        # mutating it in place leaks {int: int} keys into meta.json as
        # strings, which a reload would then silently miss).  Keyed on the
        # source object so an apply_update meta swap invalidates it.
        if self._rule_counts is None or self._rule_counts[0] is not rc:
            pairs = rc.items() if isinstance(rc, dict) else rc
            self._rule_counts = (rc, {int(r): int(c) for r, c in pairs})
        return self._rule_counts[1].get(int(rule_id), 0)

    # -- maintenance -------------------------------------------------------
    def apply_update(self, *, columns: dict = None, meta_updates: dict = None,
                     rule_postings: dict = None,
                     text_index: dict = None, fence=None) -> None:
        """Atomically swap enrichment artifacts of a sealed segment.

        Maintenance-plane entry point (backfill rewrites ``rule_bitmap`` +
        zone maps + postings).  Safe against concurrent readers:

          * spilled files are written to a temp name and ``os.replace``d, so
            a cold read sees either the old or the new file, never a torn
            one;
          * in-memory columns/postings/indexes are installed *before* the
            metadata flips, and ``self.meta`` is replaced by a single
            attribute assignment — a reader that still sees the old meta
            takes the old (fallback/scan) path, which stays byte-identical
            (**meta-flips-last** ordering: install happens-before flip
            happens-before token bump).

        ``fence`` is the maintenance plane's write barrier: a zero-arg
        callable (``LeaseManager.fence(lease)``) invoked inside the write
        lock before the first mutation.  A writer whose lease was
        superseded raises ``FencedWriteError`` here and the segment is
        untouched — two maintenance workers can never interleave writes on
        one segment.

        Safe on its own only when the new data is a pure *extension* (old
        claims still hold over the new bits).  When previously-claimed bits
        are reinterpreted, the caller must first withdraw those claims with
        a meta-only update — see ``BackfillWorker.backfill_segment``.
        """
        columns = columns or {}
        meta_updates = dict(meta_updates or {})
        for name, arr in columns.items():
            meta_updates.setdefault("columns", dict(self.meta["columns"]))
            meta_updates["columns"][name] = (str(arr.dtype), list(arr.shape))
        # the io lock excludes in-flight cold cache fills: without it a
        # reader could have loaded the OLD file and install it as the cache
        # entry AFTER the swap below, poisoning every later query
        with self._io_lock:
            if fence is not None:
                fence()     # raises FencedWriteError on a superseded lease
            if self.path is not None:
                for name, arr in columns.items():
                    _atomic_save_npy(self.path / f"{name}.npy", arr)
                if rule_postings is not None:
                    _save_index(self.path / "rule_postings.npz", rule_postings)
                if text_index is not None:
                    for fieldname, idx in text_index.items():
                        _save_index(self.path / f"{fieldname}.fts.npz", idx)
            # install data before metadata: a concurrent reader either sees
            # the old meta (-> old path, old semantics) or the new meta with
            # the new data already in place
            for name, arr in columns.items():
                if self.path is None or name in self._columns:
                    self._columns[name] = arr
            if rule_postings is not None:
                self._rule_postings = dict(rule_postings)
            if text_index is not None:
                self._text_index.update(text_index)
            self.meta = {**self.meta, **meta_updates}
            # token bump strictly AFTER the meta flip: a racing reader that
            # observes the new generation is guaranteed to also observe the
            # new meta/columns (install happens-before flip happens-before
            # bump), so nothing stale can ever be cached under a live token
            self._meta_gen += 1
            if self.path is not None:
                _atomic_write_text(self.path / "meta.json", json.dumps(
                    {**self.meta, "segment_id": self.segment_id,
                     "num_records": self.num_records},
                    default=_json_np))
        # epoch publication OUTSIDE the io lock (listeners take their own
        # locks; a listener that re-entered column() must not deadlock)
        if self._on_swap is not None:
            self._on_swap((self.segment_id,), "update", (self,))

    # -- lifecycle ---------------------------------------------------------
    def spill(self, root: Path) -> None:
        """Write one .npy per column (+ .fts.npz per indexed field)."""
        faults.fire("store.spill", segment=self.segment_id)
        with telemetry.span("store/spill", cat="store",
                            segment=int(self.segment_id)):
            d = Path(root) / f"segment-{self.segment_id:06d}"
            d.mkdir(parents=True, exist_ok=True)
            for name, arr in self._columns.items():
                np.save(d / f"{name}.npy", arr)
            for fieldname, idx in self._text_index.items():
                _save_index(d / f"{fieldname}.fts.npz", idx)
            if self._rule_postings is not None:
                _save_index(d / "rule_postings.npz", self._rule_postings)
            (d / "meta.json").write_text(json.dumps(
                {**self.meta, "segment_id": self.segment_id,
                 "num_records": self.num_records},
                default=_json_np))
        self.path = d

    def drop_caches(self) -> None:
        """Free in-memory columns/indexes (requires a spill path)."""
        if self.path is None:
            raise RuntimeError("cannot drop caches before spill()")
        with self._io_lock:
            self._columns = {}
            self._text_index = {}
            self._rule_postings = None
            # cold-run semantics extend to device residency: bumping the
            # token invalidates any device-cached copy of our columns, so a
            # cold query re-reads from disk (and is accounted as such)
            self._meta_gen += 1
        if self._on_swap is not None:
            self._on_swap((self.segment_id,), "drop", (self,))

    def nbytes(self, names=None) -> int:
        names = names or self.column_names
        total = 0
        for n in names:
            dtype, shape = self.meta["columns"][n]
            total += int(np.prod(shape)) * np.dtype(dtype).itemsize
        return total

    @staticmethod
    def load(d: Path) -> "Segment":
        meta = json.loads((Path(d) / "meta.json").read_text())
        return Segment(segment_id=meta["segment_id"],
                       num_records=meta["num_records"], meta=meta,
                       path=Path(d))


def _json_np(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(type(o))


def _save_index(path: Path, idx: dict) -> None:
    tokens = sorted(idx)
    lengths = np.asarray([len(idx[t]) for t in tokens], np.int64)
    flat = (np.concatenate([idx[t] for t in tokens]) if tokens
            else np.zeros(0, np.int32))
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "wb") as f:
        np.savez_compressed(f, tokens=np.asarray(tokens), lengths=lengths,
                            flat=flat)
    os.replace(tmp, path)


def _atomic_save_npy(path: Path, arr: np.ndarray) -> None:
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "wb") as f:
        np.save(f, arr)
    os.replace(tmp, path)


def _atomic_write_text(path: Path, text: str) -> None:
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(text)
    os.replace(tmp, path)


def _load_index(path: Path) -> dict:
    z = np.load(path, allow_pickle=False)
    tokens = [str(t) for t in z["tokens"]]
    offsets = np.concatenate([[0], np.cumsum(z["lengths"])])
    flat = z["flat"]
    return {t: flat[offsets[i]:offsets[i + 1]] for i, t in enumerate(tokens)}


class SegmentStore:
    """Append-only columnar store with sealing + spilling."""

    def __init__(self, *, segment_size: int = 100_000, root=None,
                 index_fields: tuple = (), version_rules: dict = None):
        self.segment_size = segment_size
        self.root = Path(root) if root is not None else None
        self.index_fields = tuple(index_fields)
        # engine version_id -> {str(rule_id): ident} — normally the live
        # ``StreamProcessor.version_rules`` dict (IngestPipeline wires it).
        # Lets seal derive the per-segment ``rules_known`` coverage bitmap;
        # without it segments carry no rules_known and the mapper falls back
        # to the coarser version-min check.
        self.version_rules = version_rules
        self.segments: list = []
        self._active: list = []     # pending RecordBatches
        self._active_count = 0
        self._next_id = 0           # monotonic (compaction retires ids)
        self._sealed_rows = 0       # ingest durability watermark (see WAL)
        self._lock = threading.RLock()
        # crash-safe root manifest (spilled stores only): authoritative
        # valid-segment set + durable fencing epochs.  A FRESH store over a
        # root starts with an empty manifest (first commit overwrites any
        # stale file); SegmentStore.load adopts the persisted one instead.
        self.manifest = Manifest(self.root) if self.root is not None else None
        # maintenance-epoch listeners (shared-arrangement stores): every
        # apply_update / drop_caches / replace_segments publishes the
        # affected segment ids here instead of invalidating caches in place
        self._maintenance_listeners: list = []
        # kind-aware delta listeners (standing queries, prefetching
        # arrangement stores): receive an EpochDelta for EVERY epoch,
        # including seals — the legacy segment-id feed above never saw
        # seals, because a new segment invalidates nothing
        self._epoch_listeners: list = []
        self._epoch_seq = 0

    # -- epoch publication ---------------------------------------------------
    def subscribe_maintenance(self, fn) -> None:
        """Register ``fn(segment_ids)`` to be called after every
        maintenance swap (``Segment.apply_update``), cold-run cache drop,
        or compaction retire — the shared-arrangement plane's epoch feed
        (``store.subscribe_maintenance(arrangements.publish)``).

        Idempotent per callable (N engines sharing one ArrangementStore
        publish ONE epoch per swap, not N), and bound methods are held
        weakly: a discarded engine's arrangement store is collectable — a
        store outliving its engines must not pin their device memory.

        Seals are NOT delivered here (a new segment invalidates no derived
        state); subscribe to the kind-aware ``subscribe_epochs`` feed for
        the full change stream."""
        with self._lock:
            self._subscribe_locked(self._maintenance_listeners, fn)

    def subscribe_epochs(self, fn) -> None:
        """Register ``fn(delta: EpochDelta)`` on the kind-aware epoch feed:
        every seal, enrichment swap, cache drop, compaction replace, and
        retention retire publishes one delta carrying the change kind, the
        affected segment ids, the Segment objects entering the store, and
        the post-change ``meta_token()`` of every surviving affected
        segment.  Same subscription discipline as ``subscribe_maintenance``
        (idempotent per callable, bound methods held weakly)."""
        with self._lock:
            self._subscribe_locked(self._epoch_listeners, fn)

    def _subscribe_locked(self, listeners: list, fn) -> None:
        if any(r() == fn for r in listeners):
            return
        ref = (weakref.WeakMethod(fn) if hasattr(fn, "__self__")
               else (lambda f: (lambda: f))(fn))
        listeners.append(ref)
        for s in self.segments:
            s._on_swap = self._publish_epoch

    def _publish_epoch(self, segment_ids, kind: str = "update",
                       changed=(), added=()) -> None:
        """Fan one maintenance epoch out to both feeds.  ``changed`` are
        surviving Segment objects whose artifacts swapped (update/drop);
        ``added`` are Segment objects entering the store (seal/replace).
        Always called OUTSIDE the store and segment locks — listeners take
        their own locks and may re-enter column reads."""
        _EPOCH_PUBLISHES.inc()
        telemetry.emit("epoch_publish", plane="store", change=kind,
                       segments=[int(s) for s in segment_ids])
        delta = None
        dead = False
        for r in list(self._epoch_listeners):
            fn = r()
            if fn is None:
                dead = True
                continue
            if delta is None:
                with self._lock:
                    self._epoch_seq += 1
                    seq = self._epoch_seq
                delta = EpochDelta(
                    seq=seq, kind=kind,
                    segment_ids=tuple(int(s) for s in segment_ids),
                    added=tuple(added),
                    tokens={int(s.segment_id): s.meta_token()
                            for s in (*changed, *added)})
            fn(delta)
        # legacy feed: segment ids only, and no seal deliveries (a fresh
        # segment invalidates no arrangement; publishing would spuriously
        # retire unrelated epochs' bookkeeping)
        if kind != "seal":
            for r in list(self._maintenance_listeners):
                fn = r()
                if fn is None:
                    dead = True
                else:
                    fn(tuple(segment_ids))
        if dead:
            with self._lock:
                self._maintenance_listeners = [
                    r for r in self._maintenance_listeners
                    if r() is not None]
                self._epoch_listeners = [
                    r for r in self._epoch_listeners if r() is not None]

    # -- ingestion ---------------------------------------------------------
    def append(self, batch: RecordBatch) -> None:
        sealed = []
        with self._lock:
            self._active.append(batch)
            self._active_count += len(batch)
            while self._active_count >= self.segment_size:
                sealed.append(self._seal_locked(self.segment_size))
        self._publish_seals(sealed)

    def seal(self) -> None:
        """Seal whatever is pending (end of stream)."""
        with self._lock:
            sealed = ([self._seal_locked(self._active_count)]
                      if self._active_count else [])
        self._publish_seals(sealed)

    def _publish_seals(self, sealed: list) -> None:
        """Seal epochs publish AFTER the store lock releases (listeners —
        standing-query folds — take their own locks and read columns)."""
        for seg in sealed:
            self._publish_epoch((seg.segment_id,), "seal", added=(seg,))

    def _seal_locked(self, n: int) -> Segment:
        with telemetry.span("store/seal", cat="store", rows=int(n)):
            merged = RecordBatch.concat(self._active)
            head, tail = merged.slice(0, n), merged.slice(n, len(merged))
            self._active = [tail] if len(tail) else []
            self._active_count = len(tail)
            # the watermark advances with the SAME manifest commit that
            # registers the sealed segment (one atomic write): a crash
            # can never observe a registered segment whose rows are not
            # counted, or a watermark covering rows with no registered
            # segment
            self._sealed_rows += n
            seg = self._make_segment(head, ingest_seal=True)
            self.segments.append(seg)
        return seg

    def _make_segment(self, batch: RecordBatch, register: bool = True,
                      ingest_seal: bool = False) -> Segment:
        sid = self._next_id
        self._next_id += 1
        meta = {"columns": {k: (str(v.dtype), list(v.shape))
                            for k, v in batch.columns.items()}}
        seg_postings = None
        if "timestamp" in batch.columns:
            ts = batch.columns["timestamp"]
            meta["ts_min"], meta["ts_max"] = int(ts.min()), int(ts.max())
        if ENRICH_COLUMN in batch.columns:
            bm = batch.columns[ENRICH_COLUMN]
            # zone map + per-rule counts (metadata-only count queries) +
            # sparse posting lists — the enrichment column's inverted index,
            # built once at seal; copy queries touch postings + matched rows
            enrich_meta, seg_postings = derive_enrichment_meta(bm)
            meta.update(enrich_meta)
        if ENGINE_VERSION_COLUMN in batch.columns:
            ev = batch.columns[ENGINE_VERSION_COLUMN]
            meta["engine_version_min"] = int(ev.min())
            meta["engine_version_max"] = int(ev.max())
            if self.version_rules is not None and ENRICH_COLUMN in batch.columns:
                # rule-aware coverage (maintenance plane): exactly which rule
                # identities every record's enriching engine knew
                idents = rules_known_for_versions(self.version_rules,
                                                  np.unique(ev))
                meta["rule_idents"] = idents
                meta["rules_known"] = pack_known_bitmap(
                    idents, batch.columns[ENRICH_COLUMN].shape[1])
        _SEALED.inc()
        seg = Segment(segment_id=sid, num_records=len(batch), meta=meta,
                      _columns=dict(batch.columns),
                      _rule_postings=seg_postings,
                      _on_swap=self._publish_epoch)
        for f in self.index_fields:
            if f in batch.columns:
                seg._text_index[f] = build_text_index(batch.columns[f])
        if self.root is not None:
            # spill FIRST, register second: a crash in between leaves an
            # unregistered dir that a manifest-guarded load simply ignores
            seg.spill(self.root)
            if register:
                self.manifest.commit(
                    add={sid: seg.path.name}, next_id=self._next_id,
                    sealed_rows=self._sealed_rows if ingest_seal else None)
        return seg

    # -- maintenance -------------------------------------------------------
    def make_segment_from_batch(self, batch: RecordBatch) -> Segment:
        """Build (and spill) a sealed segment outside the append path — the
        Compactor uses this to materialize a merged segment before swapping
        it into the segment list.

        The segment is deliberately NOT registered in the manifest: until
        ``replace_segments`` commits "merged in, inputs out" as one atomic
        manifest write, a crash leaves the spilled artifact invisible to
        ``SegmentStore.load`` — never loaded ALONGSIDE its un-retired
        inputs (the double-count window the manifest closes)."""
        with self._lock:
            return self._make_segment(batch, register=False)

    def replace_segments(self, old: list, new: Segment,
                         *, fence=None) -> bool:
        """Atomically substitute a contiguous run of sealed segments with
        one merged segment.  Returns False (no-op) if any of ``old`` is no
        longer present or the run is not contiguous — the caller simply
        retries next cycle.  Readers that grabbed the previous list keep
        querying the old segment objects, which stay fully valid.

        ``fence`` (a zero-arg callable, e.g. the compactor's check over
        every group member's lease) runs INSIDE the store lock before the
        swap: a writer whose leases were superseded mid-merge raises here
        and commits nothing — without it, a long merge outliving its lease
        TTL could install columns read before a newer fenced install,
        silently undoing it."""
        with self._lock:
            if fence is not None:
                fence()     # raises FencedWriteError on a superseded lease
            try:
                idx = [self.segments.index(s) for s in old]
            except ValueError:
                return False
            if idx != list(range(idx[0], idx[0] + len(idx))):
                return False
            self.segments = (self.segments[:idx[0]] + [new]
                             + self.segments[idx[0] + len(idx):])
            if self.manifest is not None:
                # THE commit point: "merged in, inputs out" lands as one
                # atomic manifest write.  A crash before this line leaves
                # the (unregistered) merged dir invisible; a crash after it
                # leaves the inputs excluded even when their RETIRED
                # tombstones below were never written — either way a reload
                # counts every record exactly once.
                self.manifest.commit(
                    add={new.segment_id: new.path.name}
                    if new.path is not None else None,
                    remove=[s.segment_id for s in old],
                    next_id=self._next_id)
        # compactor retire is a maintenance epoch: arrangements over the
        # replaced segments retire (in-flight leases pin them; the old
        # segment objects and spill files stay valid for those readers)
        self._publish_epoch([s.segment_id for s in old], "replace",
                            added=(new,))
        self._tombstone_all(old)
        return True

    def retire_segments(self, old: list, *, fence=None) -> bool:
        """Atomically remove sealed segments with no replacement — the
        retention plane's age-out path.  Same commit discipline as
        ``replace_segments`` (one manifest write is the commit point,
        tombstones are advisory, ``fence`` runs inside the lock before the
        commit); returns False when any of ``old`` is no longer present
        (raced another maintenance action — retry next cycle).  Readers
        holding the previous segment list keep querying the old objects,
        which stay fully valid until the GC collects their drained spill
        dirs."""
        with self._lock:
            if fence is not None:
                fence()     # raises FencedWriteError on a superseded lease
            if any(s not in self.segments for s in old):
                return False
            self.segments = [s for s in self.segments if s not in old]
            if self.manifest is not None:
                self.manifest.commit(
                    remove=[s.segment_id for s in old])
        self._publish_epoch([s.segment_id for s in old], "retire")
        self._tombstone_all(old)
        return True

    def _tombstone_all(self, old: list) -> None:
        failed = [s.segment_id for s in old if not self._retire_spill(s)]
        if failed and self.manifest is None:
            # pre-manifest stores rely on the tombstone alone: a live
            # un-tombstoned input would be double-loaded (and its records
            # double-counted) by the next SegmentStore.load — this must
            # not pass silently.  Manifest-guarded stores are safe either
            # way (membership already committed); the GC just loses the
            # marker it keys on.
            warnings.warn(
                f"segments {failed}: failed to tombstone replaced spill "
                f"dirs; SegmentStore.load would double-count their records",
                RuntimeWarning, stacklevel=2)

    def _retire_spill(self, seg: Segment) -> bool:
        """Tombstone a replaced segment's spill dir so ``load`` skips it.
        The files are NOT moved: in-flight cold readers holding the old
        segment object keep reading them at the same paths (renaming the
        dir would make their next ``np.load`` crash).  A future GC pass
        deletes tombstoned dirs once no reader can hold the old list."""
        if seg.path is None:
            return True
        try:
            (seg.path / RETIRED_MARKER).touch()
            return True
        except OSError as e:
            telemetry.suppressed("store.retire_spill", e)
            return False

    # -- bookkeeping ---------------------------------------------------------
    @property
    def num_records(self) -> int:
        with self._lock:
            return sum(s.num_records for s in self.segments) + self._active_count

    @property
    def sealed_rows(self) -> int:
        """Total rows the ingest path has sealed into registered segments
        — the durability watermark the ingest WAL truncates against.
        Monotonic across the store's lifetime (compaction/retention change
        membership, never this counter)."""
        with self._lock:
            return self._sealed_rows

    def account_skipped_rows(self, n: int) -> None:
        """Advance the ingest durability watermark past ``n`` source rows
        that will never be appended (the pipeline quarantined them after
        both match lanes failed).  Seals any pending rows first so the
        watermark stays prefix-accurate: W always means source rows
        [0, W) are durable — in a registered segment or in quarantine."""
        sealed = []
        with self._lock:
            if self._active_count:
                sealed.append(self._seal_locked(self._active_count))
            self._sealed_rows += int(n)
            if self.manifest is not None:
                self.manifest.commit(sealed_rows=self._sealed_rows)
        self._publish_seals(sealed)

    def drop_caches(self) -> None:
        """Cold-run control: all sealed segments forget in-memory data."""
        for s in self.segments:
            s.drop_caches()

    def refresh(self) -> dict:
        """Converge this (rooted) store onto the on-disk world another
        *process* may have advanced — the read side of the multi-process
        topology, where maintenance workers and the ingest parent hold
        independent ``SegmentStore`` objects over one root.

        Three deltas are reconciled against the persisted manifest and the
        per-segment ``meta.json`` files (each written atomically, so every
        read here sees a consistent before-or-after state):

          * **added** — segments the manifest lists that this store has
            never loaded (another process sealed or compacted them in);
            loaded and published as ``seal`` epochs;
          * **removed** — in-memory segments the manifest no longer lists
            (another process compacted/retired them); dropped from the
            segment list and published as ``retire`` epochs;
          * **updated** — spilled segments whose on-disk ``meta.json``
            differs from the in-memory meta (another process's backfill
            ``apply_update`` swapped enrichment artifacts); the new meta is
            installed under the segment's io lock, caches are purged, the
            meta token bumps, and an ``update`` epoch publishes — exactly
            the invalidation discipline an in-process swap follows.

        Deliberately does NOT touch ``self.manifest``'s in-memory state:
        this store's own pending commits (e.g. a seal racing the refresh)
        must never be rolled back by re-adopting a snapshot.  In the
        supported topology the manifest has a single writer process;
        refresh only reconciles *membership and artifacts* for readers.

        Returns ``{"added": [...], "removed": [...], "updated": [...]}``
        segment-id lists.  No-op (empty deltas) for rootless stores.
        """
        empty = {"added": [], "removed": [], "updated": []}
        if self.root is None:
            return empty
        persisted = Manifest.read(self.root)
        if persisted is None:
            return empty
        on_disk = {int(s): str(name)
                   for s, name in persisted.get("segments", {}).items()}
        added, removed, updated = [], [], []
        with self._lock:
            have = {s.segment_id: s for s in self.segments}
            for sid in sorted(have):
                if sid not in on_disk:
                    removed.append(have[sid])
            for sid, name in sorted(on_disk.items()):
                if sid in have:
                    continue
                d = self.root / name
                if not d.exists():
                    continue    # mid-commit window; next refresh sees it
                seg = Segment.load(d)
                seg._on_swap = self._publish_epoch
                added.append(seg)
            if removed:
                gone = {s.segment_id for s in removed}
                self.segments = [s for s in self.segments
                                 if s.segment_id not in gone]
            self.segments.extend(added)
            self._next_id = max(self._next_id,
                                int(persisted.get("next_id", 0)))
        for sid, seg in sorted(have.items()):
            if sid not in on_disk or seg.path is None:
                continue
            try:
                disk_meta = json.loads((seg.path / "meta.json").read_text())
            except (FileNotFoundError, ValueError):
                continue
            # normalize the in-memory meta through the same JSON round-trip
            # the spill path uses, so an unchanged segment compares equal
            cur = json.loads(json.dumps(
                {**seg.meta, "segment_id": seg.segment_id,
                 "num_records": seg.num_records}, default=_json_np))
            if disk_meta == cur:
                continue
            with seg._io_lock:
                seg.meta = disk_meta
                seg._columns = {}
                seg._text_index = {}
                seg._rule_postings = None
                seg._rule_counts = None
                seg._meta_gen += 1
            updated.append(seg)
        # epoch publication outside every lock, mirroring the in-process
        # paths: seals for arrivals, retire for departures, one update
        # epoch covering every artifact swap
        for seg in added:
            self._publish_epoch((seg.segment_id,), "seal", added=(seg,))
        if removed:
            self._publish_epoch([s.segment_id for s in removed], "retire")
        if updated:
            self._publish_epoch([s.segment_id for s in updated], "update",
                                changed=tuple(updated))
        return {"added": [s.segment_id for s in added],
                "removed": [s.segment_id for s in removed],
                "updated": [s.segment_id for s in updated]}

    def storage_nbytes(self, names=None) -> int:
        return sum(s.nbytes(names) for s in self.segments)

    @staticmethod
    def load(root, *, segment_size: int = 100_000,
             index_fields: tuple = (), version_rules: dict = None
             ) -> "SegmentStore":
        """Reopen a spilled store.  When a root manifest exists it is
        authoritative: exactly the manifest's valid-segment set is loaded
        (closing the compaction double-count window — a crash between
        spilling a merged segment and tombstoning its inputs leaves both
        on disk, but only one side is ever in the manifest).  Pre-manifest
        stores fall back to directory scanning with RETIRED-tombstone
        skipping, and are upgraded: the adopted set is committed as their
        first manifest.

        ``segment_size``/``index_fields``/``version_rules`` configure the
        reopened store's FUTURE seals (persisted segments carry their
        own); an ingest restart must pass the same settings it ingests
        with — constructing a fresh ``SegmentStore`` over a populated
        root instead would start an empty manifest whose first commit
        disowns every already-committed segment."""
        store = SegmentStore(root=root, segment_size=segment_size,
                             index_fields=index_fields,
                             version_rules=version_rules)
        persisted = Manifest.read(root)
        if persisted is not None:
            store.manifest.adopt(persisted)
            dirs = []
            for d in store.manifest.segment_dirs():
                if d.exists():
                    dirs.append(d)
                else:
                    # the manifest is the authority on what SHOULD exist:
                    # a listed dir gone missing is data loss (external
                    # deletion, partial restore) and must not reload as a
                    # silently smaller store — the mirror hazard of the
                    # double-count window the manifest closes
                    _SEGMENTS_MISSING.inc()
                    telemetry.emit("segment_missing", plane="store",
                                   dir=d.name, root=str(root))
                    warnings.warn(
                        f"manifest lists {d.name} but the spill dir is "
                        f"missing; its records are LOST from this load",
                        RuntimeWarning, stacklevel=2)
        elif (Path(root) / INGEST_WAL_DIRNAME).exists():
            # a WAL dir proves this store was born under manifest
            # discipline: no manifest on disk means the process died before
            # the FIRST commit, so any spilled segment dir is an
            # uncommitted orphan whose rows the journal still holds.
            # Adopting it would double-ingest on replay — recovery re-seals
            # (and overwrites) it from the WAL instead.
            dirs = []
        else:
            dirs = [d for d in sorted(Path(root).glob("segment-*"))
                    if not (d / RETIRED_MARKER).exists()]
        for d in dirs:
            seg = Segment.load(d)
            seg._on_swap = store._publish_epoch
            store.segments.append(seg)
        store._next_id = max(
            store.manifest.next_id(),
            1 + max((s.segment_id for s in store.segments), default=-1))
        store._sealed_rows = store.manifest.sealed_rows()
        if persisted is None and store.segments:
            store.manifest.commit(
                add={s.segment_id: s.path.name for s in store.segments},
                next_id=store._next_id)
        return store
