"""Shared-arrangement device plane — one refcounted, epoch-versioned device
arrangement per (segment set, word subset), leased by ALL in-flight queries.

Shared Arrangements (McSherry et al.) observes that concurrency over
overlapping data scales only when concurrent readers share ONE maintained
arrangement instead of each materializing a private copy; Functional
Isolation (Zapridou et al.) adds that the shared substrate must still
isolate per-query execution.  Here the arrangement is the stacked device
image of the enrichment-bitmap WORD columns a query family touches:

  * ``ArrangementStore`` pools device word columns keyed by
    ``(Segment.meta_token(), word)`` and assembles them into stacked
    ``Arrangement``s keyed by ``(segment-token tuple, word tuple)`` —
    each word column crosses the H2D link **once per maintenance epoch**,
    no matter how many queries (or shards) are in flight over it;
  * queries access an arrangement only through an RAII-style
    ``ArrangementLease`` (refcount up on acquire, down on release, leaks
    detected at finalization) — per-query execution state stays private,
    only the immutable device image is shared;
  * maintenance (``Segment.apply_update``, ``SegmentStore.
    replace_segments``, compactor retire, cold-run cache drops)
    **publishes a new epoch** instead of invalidating in place: the
    affected arrangements and pooled columns are *retired* — in-flight
    leases pin them (readers never observe a torn swap), new queries bind
    the new epoch's tokens and build fresh entries, and a retired entry
    frees its device memory deterministically the moment its refcount
    drains.

Accounting (``uploads``, ``h2d_bytes``, ``device_bytes`` /
``device_bytes_peak``) is first-class so tests can assert the
once-per-epoch upload discipline and benchmarks can report H2D traffic and
device-memory high-water per sharing regime.
"""
from __future__ import annotations

import threading
import warnings
from collections import Counter
from dataclasses import dataclass

from repro.core import telemetry

# Process-wide telemetry ALONGSIDE the per-object accounting: tests and
# benchmarks keep reading per-store ``uploads``/``h2d_bytes``/... values;
# the registry aggregates across every store in the process.
_T_UPLOADS = telemetry.counter(
    "fluxsieve_arrangement_uploads_total",
    help="Word-column H2D uploads into the shared device pool.")
_T_H2D_BYTES = telemetry.counter(
    "fluxsieve_arrangement_h2d_bytes_total",
    help="Bytes crossing the H2D link for arrangement columns.")
_T_BUILDS = telemetry.counter(
    "fluxsieve_arrangement_builds_total",
    help="Arrangement assemblies (stack builds).")
_T_LEASE_HITS = telemetry.counter(
    "fluxsieve_arrangement_lease_hits_total",
    help="Leases served from an already-live arrangement.")
_T_EVICT_ARR = telemetry.counter(
    "fluxsieve_arrangement_evictions_total",
    labels={"kind": "arrangement"},
    help="Evictions from the shared device plane, by kind.")
_T_EVICT_COL = telemetry.counter(
    "fluxsieve_arrangement_evictions_total", labels={"kind": "column"})
_T_EPOCHS = telemetry.counter(
    "fluxsieve_arrangement_epochs_total",
    help="Maintenance epochs published to the device plane.")
_T_RETIRED = telemetry.counter(
    "fluxsieve_arrangement_epoch_retirements_total",
    help="Arrangements retired by an epoch publication.")
_T_LEAKS = telemetry.counter(
    "fluxsieve_arrangement_lease_leaks_total",
    help="Leases released at finalization instead of by their owner.")
_T_PREFETCH = telemetry.counter(
    "fluxsieve_arrangement_prefetch_total",
    help="Arrangements rebuilt eagerly on epoch publish (off the query "
         "path), so the first post-swap query skips the cold build.")
_DEV_BYTES = telemetry.gauge(
    "fluxsieve_arrangement_device_bytes",
    help="Device bytes resident across all arrangement stores.")
_DEV_PEAK = telemetry.gauge(
    "fluxsieve_arrangement_device_bytes_peak",
    help="High-water mark of resident arrangement device bytes.")


@dataclass(frozen=True)
class ArrangementItem:
    """One segment's contribution to an arrangement build.

    ``token`` is the segment's ``meta_token()`` read at lease-key time —
    BEFORE ``load`` touches the host column — so a racing maintenance swap
    can only pool new data under an already-dead token, never stale data
    under a live one (the same discipline the executor's snapshot
    validation relies on).  ``load`` returns the host ``(N, W)`` bitmap and
    is invoked only on a pool miss, at most once per build per segment."""
    token: tuple
    num_records: int
    load: object


class _DeviceColumn:
    """Pooled device word column: ``refs`` counts live arrangements built
    over it; ``retired`` marks its token dead (freed once refs drain)."""

    __slots__ = ("key", "arr", "nbytes", "refs", "retired")

    def __init__(self, key, arr, nbytes: int):
        self.key = key
        self.arr = arr
        self.nbytes = int(nbytes)
        self.refs = 0
        self.retired = False


class Arrangement:
    """One epoch-stamped stacked device image: ``stack`` is the
    ``(bucket_n(sum lens), P)`` uint32 concatenation of every segment's
    gathered word columns, ``row_seg`` the padded per-row segment-slot
    vector, ``lens`` the unpadded per-segment record counts."""

    __slots__ = ("key", "tokens", "words", "epoch", "stack", "row_seg",
                 "lens", "columns", "nbytes", "refcount", "retired",
                 "block_n")

    def __init__(self, key, epoch, stack, row_seg, lens, columns, nbytes,
                 block_n: int = 1024):
        self.key = key
        self.tokens, self.words = key
        self.epoch = epoch
        self.stack = stack
        self.row_seg = row_seg
        self.lens = lens
        self.columns = columns          # pooled _DeviceColumns we hold refs on
        self.nbytes = nbytes            # stack + row_seg (columns accounted
        self.refcount = 0               # separately in the pool)
        self.retired = False
        self.block_n = block_n          # padding bucket (prefetch rebuilds
                                        # reproduce the family's key shape)


class ArrangementLease:
    """RAII handle on a shared arrangement.  Release exactly once (context
    manager or explicit ``release()``); a lease collected unreleased is a
    bug — it is released at finalization with a ``ResourceWarning`` naming
    the owning worker so leaks are attributable, not silent pins."""

    __slots__ = ("arrangement", "owner", "hit", "_store", "_released",
                 "__weakref__")

    def __init__(self, arrangement: Arrangement, owner: str, store):
        self.arrangement = arrangement
        self.owner = owner
        self.hit = False            # leased a live arrangement, no build
        self._store = store
        self._released = False

    @property
    def released(self) -> bool:
        return self._released

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        if self._store is not None:
            self._store._release(self)

    def __enter__(self) -> "ArrangementLease":
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def __del__(self):
        if not self._released:
            if self._store is not None:
                self._store.leaks += 1
            try:    # interpreter teardown may have torn telemetry down
                _T_LEAKS.inc()
                telemetry.emit("lease_leak", plane="arrangement",
                               owner=self.owner,
                               key=repr(self.arrangement.key))
            except Exception as e:  # noqa: BLE001
                telemetry.suppressed("arrangement.lease_leak_emit", e)
            warnings.warn(
                f"ArrangementLease leaked by {self.owner!r} "
                f"(key={self.arrangement.key!r}) — released at finalization",
                ResourceWarning, stacklevel=1)
            self.release()


class ArrangementStore:
    """The shared device plane.  Thread-safe; one instance is shared by
    every executor shard and (typically) every engine over one
    ``SegmentStore`` — wire maintenance with
    ``segment_store.subscribe_epochs(arrangements.on_epoch)`` (the
    kind-aware feed; the legacy
    ``subscribe_maintenance(arrangements.publish)`` wiring still works) so
    swaps publish epochs here instead of invalidating anything in place.

    ``max_live`` bounds the number of DISTINCT live arrangements (query
    families); evicting one only retires it — leased readers keep it alive
    until their refcounts drain.  ``max_pool_columns`` bounds the device
    column pool (LRU over unreferenced columns): the once-per-epoch upload
    guarantee holds while the working set fits the pool; beyond it, the
    coldest unreferenced columns re-upload on next use instead of growing
    device residency monotonically between epochs."""

    def __init__(self, *, max_live: int = 32, max_pool_columns: int = 1024):
        self.max_live = max_live
        self.max_pool_columns = max_pool_columns
        self._lock = threading.Lock()
        self._epoch = 0
        self._live = {}             # key -> Arrangement (insertion-ordered)
        self._building = {}         # key -> threading.Event
        self._doomed_builds = set()  # keys published-over while building
        self._columns = {}          # (token, word) -> _DeviceColumn, in LRU
                                    # order (moved to end on every hit)
        self._pool_index = {}       # (segment_id, word) -> current column
        # accounting
        self.uploads: Counter = Counter()   # (token, word) -> H2D uploads
        self.h2d_bytes = 0
        self.device_bytes = 0
        self.device_bytes_peak = 0
        self.builds = 0
        self.lease_hits = 0
        self.leaks = 0
        self.prefetches = 0
        self._lease_owners: Counter = Counter()
        # prefetch source (set via set_prefetch_source): segment_id ->
        # ArrangementItem with the segment's CURRENT token, or None when
        # the segment left the store.  Enables eager post-swap rebuilds.
        self._prefetch_source = None

    # -- epoch plane -------------------------------------------------------
    @property
    def epoch(self) -> int:
        return self._epoch

    def publish(self, segment_ids=None) -> int:
        """Maintenance epoch publication: retire every arrangement (and
        pooled column) touching ``segment_ids`` (``None`` = all).  Nothing
        is freed under a reader — retired entries with live refcounts
        survive until they drain; drained ones free immediately.  Returns
        the new epoch."""
        epoch, _ = self._publish_collect(segment_ids)
        return epoch

    def set_prefetch_source(self, fn) -> None:
        """Arm epoch-publish prefetch: ``fn(segment_id)`` must return an
        ``ArrangementItem`` bound to the segment's current token (or None
        when the segment is gone).  With a source set, ``on_epoch`` eagerly
        rebuilds the arrangements an ``update`` epoch retired — off the
        query path, so the first post-swap query leases a hot entry
        instead of paying the cold build."""
        self._prefetch_source = fn

    def on_epoch(self, delta) -> None:
        """Kind-aware epoch feed entry (``store.subscribe_epochs``
        target).  Seals publish nothing here — a new segment invalidates
        no arrangement.  Cache drops retire WITHOUT prefetching (cold-run
        semantics: re-warming device state would un-drop the caches);
        updates retire and, when a prefetch source is armed, rebuild the
        retired live arrangements under the swapped segments' new
        tokens."""
        if delta.kind == "seal":
            return
        _, retired = self._publish_collect(delta.segment_ids)
        if delta.kind == "update" and self._prefetch_source is not None:
            self._prefetch(retired)

    def _publish_collect(self, segment_ids) -> tuple:
        """publish() + the retired live arrangements' rebuild specs
        ``[(tokens, words, block_n)]`` (prefetch input)."""
        ids = None if segment_ids is None else {int(s) for s in segment_ids}

        def touches(tokens):
            return ids is None or any(t[0] in ids for t in tokens)

        retired = []
        with self._lock:
            self._epoch += 1
            _T_EPOCHS.inc()
            for key in [k for k, a in self._live.items()
                        if touches(a.tokens)]:
                arr = self._live.pop(key)
                retired.append((arr.tokens, arr.words, arr.block_n))
                self._retire_locked(arr)
                _T_RETIRED.inc()
            # a build in flight over the published segments must not enter
            # _live as a fresh entry: its key is marked doomed and the
            # finished arrangement installs already-retired (its lease
            # stays readable; the executor's snapshot check governs reuse)
            for key in self._building:
                if touches(key[0]):
                    self._doomed_builds.add(key)
            for ck in [ck for ck, c in self._columns.items()
                       if ids is None or ck[0][0] in ids]:
                col = self._columns[ck]
                col.retired = True
                if col.refs == 0:
                    self._remove_column_locked(col)
            return self._epoch, retired

    def _prefetch(self, retired: list) -> None:
        """Rebuild each retired live arrangement under the current tokens:
        swapped segments resolve fresh items (new token -> fresh upload),
        untouched ones keep their pooled columns, and the lease/release
        installs the entry at refcount 0 — exactly what the next query of
        the new epoch leases without building.  Best-effort: a segment
        that left the store or a failed build skips that family."""
        source = self._prefetch_source
        for tokens, words, block_n in retired:
            try:
                items = [source(t[0]) for t in tokens]
                if any(it is None for it in items):
                    continue        # a member segment left the store
                self.lease(items, words, block_n=block_n,
                           owner="prefetch").release()
                self.prefetches += 1
                _T_PREFETCH.inc()
                telemetry.emit("arrangement_prefetch", plane="arrangement",
                               segments=len(items), words=len(words))
            except Exception as e:  # noqa: BLE001 — prefetch is advisory
                telemetry.suppressed("arrangement.prefetch", e)

    # -- lease plane -------------------------------------------------------
    def lease(self, items, words, *, block_n: int = 1024,
              owner: str = "query") -> ArrangementLease:
        """Acquire (building if absent) the arrangement for these segments
        and word columns.  Concurrent leases of one key coalesce into a
        single build — the others block until it is published, so N
        clients cost one upload per word column, not N."""
        key = (tuple(i.token for i in items), tuple(words))
        while True:
            with self._lock:
                arr = self._live.get(key)
                if arr is not None:
                    arr.refcount += 1
                    self.lease_hits += 1
                    _T_LEASE_HITS.inc()
                    lease = self._make_lease_locked(arr, owner)
                    lease.hit = True
                    return lease
                ev = self._building.get(key)
                if ev is None:
                    self._building[key] = ev = threading.Event()
                    break
            ev.wait()
        try:
            arr = self._build(key, items, words, block_n)
        except BaseException:
            with self._lock:
                self._building.pop(key, None)
                self._doomed_builds.discard(key)
            ev.set()                # waiters retry (one becomes the builder)
            raise
        # install atomically with clearing the build marker, BEFORE waking
        # waiters: a racing client always sees the key in _building or in
        # _live, so a finished build can never be silently overwritten by a
        # duplicate (which would orphan its bytes and column refs)
        with self._lock:
            self._building.pop(key, None)
            doomed = key in self._doomed_builds
            self._doomed_builds.discard(key)
            # a publish raced the build: the lease stays valid (tokens were
            # read before the swap; the executor's snapshot validation
            # decides whether the RESULT is reusable) but the arrangement
            # installs retired — it frees when this lease drains instead of
            # squatting a _live slot under dead tokens
            if doomed:
                arr.retired = True
            else:
                self._live[key] = arr
                self._evict_locked()
            arr.refcount += 1
            lease = self._make_lease_locked(arr, owner)
        ev.set()
        return lease

    def build_ephemeral(self, items, words, *, block_n: int = 1024,
                        owner: str = "cold") -> ArrangementLease:
        """Cold-run build: nothing pooled, nothing counted as shared-plane
        traffic — models a query that must pay the full upload itself."""
        stack, row_seg, lens, nbytes = self._assemble(
            items, words, block_n, pooled=False)
        arr = Arrangement((tuple(i.token for i in items), tuple(words)),
                          self._epoch, stack, row_seg, lens, (), nbytes,
                          block_n)
        arr.retired = True              # frees as soon as the lease drops
        arr.refcount = 1
        with self._lock:
            self._alloc_bytes(nbytes)   # balanced by the release-time free
            return self._make_lease_locked(arr, owner)

    def active_leases(self) -> dict:
        """owner -> live lease count (leak visibility per worker ident)."""
        with self._lock:
            return {o: n for o, n in self._lease_owners.items() if n}

    def live_arrangements(self) -> int:
        with self._lock:
            return len(self._live)

    def upload_counts(self) -> dict:
        """(segment token, word) -> H2D uploads.  The shared-arrangement
        invariant is every value == 1: one upload per word column per
        maintenance epoch (a swap issues a NEW token, hence a new key)."""
        with self._lock:
            return dict(self.uploads)

    def pinned_segment_ids(self) -> set:
        """Segment ids still referenced by in-flight readers — the
        epoch-drain signal the spill GC consults before deleting a RETIRED
        segment's directory.  A pooled column with ``refs > 0`` belongs to
        at least one live arrangement (every pooled build refs its
        columns, and an arrangement's columns drain exactly when its last
        lease releases), so scanning referenced columns covers every
        leased arrangement, retired or live."""
        with self._lock:
            return {ck[0][0] for ck, col in self._columns.items()
                    if col.refs > 0}

    # -- internals ---------------------------------------------------------
    def _make_lease_locked(self, arr, owner):
        self._lease_owners[owner] += 1
        return ArrangementLease(arr, owner, self)

    def _release(self, lease: ArrangementLease) -> None:
        with self._lock:
            self._lease_owners[lease.owner] -= 1
            arr = lease.arrangement
            arr.refcount -= 1
            if arr.refcount == 0 and arr.retired:
                self._free_arrangement_locked(arr)

    def _retire_locked(self, arr: Arrangement) -> None:
        arr.retired = True
        if arr.refcount == 0:
            self._free_arrangement_locked(arr)

    def _free_arrangement_locked(self, arr: Arrangement) -> None:
        self._free_bytes(arr.nbytes)
        arr.stack = arr.row_seg = None      # drop device buffers
        for col in arr.columns:
            col.refs -= 1
            if col.refs == 0 and col.retired:
                self._remove_column_locked(col)
        arr.columns = ()

    def _remove_column_locked(self, col: _DeviceColumn) -> None:
        if self._columns.get(col.key) is col:
            del self._columns[col.key]
        iw = (col.key[0][0], col.key[1])
        if self._pool_index.get(iw) is col:
            del self._pool_index[iw]
        self._free_bytes(col.nbytes)
        col.arr = None

    def _evict_columns_locked(self) -> None:
        """LRU-bound the pool: drop the coldest UNREFERENCED live columns
        (retired ones free on drain; referenced ones belong to live
        arrangements).  An evicted column simply re-uploads on next use."""
        if len(self._columns) <= self.max_pool_columns:
            return
        for ck in list(self._columns):
            if len(self._columns) <= self.max_pool_columns:
                break
            col = self._columns[ck]
            if col.refs == 0 and not col.retired:
                self._remove_column_locked(col)
                _T_EVICT_COL.inc()

    def _evict_locked(self) -> None:
        while len(self._live) > self.max_live:
            # cost-weighted: evict the CHEAPEST-to-rebuild arrangement
            # (device bytes proxy its upload+assembly cost), so expensive
            # families stay resident under pressure.  Ties break on
            # insertion order (oldest first).  Leased readers keep the
            # evicted entry alive until their refcounts drain.
            key = min(self._live, key=lambda k: self._live[k].nbytes)
            self._retire_locked(self._live.pop(key))
            _T_EVICT_ARR.inc()

    def _alloc_bytes(self, n: int) -> None:
        self.device_bytes += int(n)
        self.device_bytes_peak = max(self.device_bytes_peak,
                                     self.device_bytes)
        _DEV_PEAK.track_max(_DEV_BYTES.inc(int(n)))

    def _free_bytes(self, n: int) -> None:
        self.device_bytes -= int(n)
        _DEV_BYTES.dec(int(n))

    def _build(self, key, items, words, block_n) -> Arrangement:
        stack, row_seg, lens, nbytes = self._assemble(
            items, words, block_n, pooled=True)
        with self._lock:
            self.builds += 1
            _T_BUILDS.inc()
            cols = []
            for it in items:
                for w in words:
                    col = self._columns.get((it.token, w))
                    if col is not None:
                        col.refs += 1
                        cols.append(col)
            arr = Arrangement(key, self._epoch, stack, row_seg, lens,
                              tuple(cols), nbytes, block_n)
            self._alloc_bytes(nbytes)
            return arr

    def _assemble(self, items, words, block_n, *, pooled: bool):
        """Gather/upload the word columns and assemble the padded stack.
        All eager device ops in the query plane live HERE, once per
        arrangement — a hot query is one jitted dispatch plus one D2H."""
        import jax.numpy as jnp
        import numpy as np

        from repro.kernels.dfa_scan.ops import bucket_n

        parts, lens = [], []
        for it in items:
            host = None
            cols = []
            for w in words:
                dev = self._pool_get((it.token, w)) if pooled else None
                if dev is None:
                    if host is None:
                        host = np.asarray(it.load())
                    dev = jnp.asarray(np.ascontiguousarray(host[:, w]))
                    if pooled:
                        dev = self._pool_put((it.token, w), dev)
                cols.append(dev)
            parts.append(cols[0][:, None] if len(cols) == 1
                         else jnp.stack(cols, axis=1))
            lens.append(int(it.num_records))
        stack = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        row_seg = np.repeat(np.arange(len(items), dtype=np.int32), lens)
        n_pad = bucket_n(stack.shape[0], block_n)
        if n_pad != stack.shape[0]:
            stack = jnp.pad(stack, ((0, n_pad - stack.shape[0]), (0, 0)))
            row_seg = np.pad(row_seg, (0, n_pad - len(row_seg)))
        row_seg = jnp.asarray(row_seg)
        nbytes = int(stack.size) * 4 + int(row_seg.size) * 4
        return stack, row_seg, tuple(lens), nbytes

    def _pool_get(self, ck):
        with self._lock:
            col = self._columns.get(ck)
            if col is None or col.retired:
                return None
            self._columns.pop(ck)           # LRU bump: move to the end
            self._columns[ck] = col
            return col.arr

    def _pool_put(self, ck, dev):
        """Install an uploaded column; a concurrent build of an overlapping
        key may have won the race — its copy is kept (and only its upload
        counted) so the pool never holds two live copies of one column."""
        nbytes = int(dev.size) * 4
        with self._lock:
            col = self._columns.get(ck)
            if col is not None and not col.retired:
                return col.arr
            # supersede a retired predecessor (older token, same segment +
            # word) still pinned by readers — O(1) via the pool index
            iw = (ck[0][0], ck[1])
            prev = self._pool_index.get(iw)
            if prev is not None and prev.key != ck:
                prev.retired = True
                if prev.refs == 0:
                    self._remove_column_locked(prev)
            col = _DeviceColumn(ck, dev, nbytes)
            self._columns[ck] = col
            self._pool_index[iw] = col
            self.uploads[ck] += 1
            self.h2d_bytes += nbytes
            _T_UPLOADS.inc()
            _T_H2D_BYTES.inc(nbytes)
            self._alloc_bytes(nbytes)
            self._evict_columns_locked()
            return dev
