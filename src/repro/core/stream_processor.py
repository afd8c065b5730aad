"""StreamProcessor — the in-stream prefilter/enricher (paper §3.2 module 2,
§3.4.3 "Streaming Application (Matcher)").

Dual-topology design, as in the paper's Kafka Streams implementation:

  * the **data topology** (``process``) runs every incoming RecordBatch
    through the active per-field matchers and attaches the packed rule
    bitmap (enrichment) — and, in ``filter`` mode, drops non-matching
    records entirely;
  * the **control topology** (``poll_updates``) consumes engine-update
    notifications, fetches the compiled artifact from the object store,
    validates version + checksum, and hot-swaps the active matchers.

The active engine lives behind a single reference read once per batch
(`_active`), so in-flight batches finish against the engine they started
with — the paper's no-downtime swap guarantee.  Swap never retraces jit
caches because table shapes are bucketed (automaton.py).

The data topology is split into ``process_async`` (ONE fused device
dispatch for all text fields of a batch — see matcher.FusedMatcher) and
``finalize`` (single D2H transfer + column attach + optional filter), so a
pipelined caller can keep the device matching batch *k* while the host
stores batch *k-1* (data/pipeline.py).  ``process`` is the sequential
composition of the two.
"""
from __future__ import annotations

import functools
import operator
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from repro.core import enrichment, faults, telemetry
from repro.core.control_plane import (ControlBus, MATCHER_ACKS,
                                      MATCHER_UPDATES)
from repro.core.faults import CircuitBreaker, InjectedCrash
from repro.core.matcher import (FUSED_BACKENDS, EngineBundle, FusedMatcher,
                                MatchResult, build_matchers, match_pairs)
from repro.core.object_store import ObjectRef, ObjectStore
from repro.core.patterns import ruleset_idents
from repro.core.records import RecordBatch

ENRICH_COLUMN = "rule_bitmap"
ENGINE_VERSION_COLUMN = "engine_version_id"

# the oracle lane the breaker degrades to: same compiled tables, jnp
# reference execution — bitmaps identical to the primary by construction
FALLBACK_BACKEND = "dfa_ref"

_DISPATCH_ERRORS = telemetry.counter(
    "fluxsieve_match_dispatch_errors_total",
    help="Failed primary-lane dispatch attempts (each may be retried).")
_FALLBACK_BATCHES = telemetry.counter(
    "fluxsieve_match_fallback_batches_total",
    help="Batches matched on the degraded oracle lane (breaker open or "
         "primary retries exhausted).")
_POLL_HIST = telemetry.histogram(
    "fluxsieve_match_poll_seconds",
    help="Control-topology bus-poll latency (poll_updates, per call).")


class BatchMatchError(RuntimeError):
    """A batch failed on the primary AND the fallback match lanes — it
    cannot be enriched.  The ingest pipeline quarantines such batches to a
    dead-letter spill dir instead of dropping them (or crashing)."""


@dataclass
class _Active:
    bundle: EngineBundle
    matchers: dict          # field -> MatchEngine
    fused: object           # FusedMatcher, or None for host-path backends
    version_id: int         # monotonically increasing local id
    activated_at: float
    fallback: object = None  # lazily built FALLBACK_BACKEND FusedMatcher


@dataclass
class PendingBatch:
    """An in-flight enriched batch: dispatched, result possibly still on
    device.  ``StreamProcessor.finalize`` turns it into a RecordBatch."""
    batch: RecordBatch
    result: MatchResult
    version_id: int
    n: int


@dataclass
class ProcessorStats:
    records_in: int = 0
    records_out: int = 0
    records_matched: int = 0
    batches: int = 0
    swaps: int = 0
    match_seconds: float = 0.0
    versions: dict = field(default_factory=dict)  # version -> activation time


class StreamProcessor:
    """mode: 'enrich' keeps every record and attaches the bitmap (paper's
    deployment — analytical plane stays the complete source of truth);
    'filter' additionally drops records that match no rule (pre-filtering
    for pipelines that only want query-relevant records)."""

    def __init__(self, bundle: EngineBundle, *, instance_id: str = "proc-0",
                 mode: str = "enrich", backend: str = "dfa_ref",
                 bus: ControlBus = None, store: ObjectStore = None,
                 block_n: int = 256,
                 confirm_backend: str = "ref", retry_limit: int = 2,
                 retry_backoff_s: float = 0.002,
                 breaker: CircuitBreaker = None):
        if mode not in ("enrich", "filter"):
            raise ValueError(mode)
        self.instance_id = instance_id
        self.mode = mode
        self.backend = backend
        self.block_n = block_n
        self.confirm_backend = confirm_backend   # dfa_selective pass 2
        self.bus = bus
        self.store = store
        # graceful degradation: bounded retry-with-backoff around the
        # primary dispatch, then a circuit breaker that routes whole
        # batches to the FALLBACK_BACKEND oracle lane (see _dispatch)
        self.retry_limit = int(retry_limit)
        self.retry_backoff_s = float(retry_backoff_s)
        self.breaker = breaker or CircuitBreaker(site="match.dispatch")
        self.stats = ProcessorStats()
        self._lock = threading.RLock()
        self._pending: dict = {}          # version -> ObjectRef (fetch queued)
        self._swap_lock = threading.Lock()
        # version_id -> {str(rule_id): ident}: which rules (by content
        # identity) each activated engine knew.  The SegmentStore reads this
        # at seal time to derive the per-segment ``rules_known`` coverage
        # metadata (consistency propagation, paper §3.4 step 4).
        self.version_rules: dict = {}
        self._install(bundle, version_id=0)

    # -- data topology ---------------------------------------------------
    def process(self, batch: RecordBatch) -> RecordBatch:
        """Match + enrich (and maybe filter) one batch, synchronously."""
        return self.finalize(self.process_async(batch))

    def process_async(self, batch: RecordBatch) -> PendingBatch:
        """Dispatch the match for one batch and return without blocking on
        the device: ONE fused dispatch covering every matched text field
        (bitmap OR + any-match mask computed on device)."""
        active = self._active                      # single read: swap-safe
        t0 = time.perf_counter()
        n = len(batch)
        result = self._dispatch(active, batch, n)
        with self._lock:
            self.stats.match_seconds += time.perf_counter() - t0
        return PendingBatch(batch=batch, result=result,
                            version_id=active.version_id, n=n)

    def _dispatch(self, active: _Active, batch: RecordBatch, n: int):
        """Primary-lane dispatch behind the degradation machinery: bounded
        retry-with-backoff, then the circuit breaker routes the batch to
        the oracle lane (same bundle, FALLBACK_BACKEND execution — bitmaps
        identical by construction).  While OPEN, every batch goes straight
        to the fallback and periodic HALF_OPEN probes test the primary.
        A batch that fails on BOTH lanes raises ``BatchMatchError`` — the
        pipeline quarantines it, ingest keeps flowing."""
        def primary():
            faults.fire("match.dispatch", backend=self.backend,
                        instance=self.instance_id)
            if active.fused is not None:
                return active.fused.match_batch(batch.columns,
                                                batch.text_fields, n)
            return self._match_per_field(active, batch)

        if self.breaker.allow_primary():
            err = None
            for attempt in range(self.retry_limit + 1):
                try:
                    result = primary()
                    self.breaker.record_success()
                    return result
                except InjectedCrash:
                    raise               # a simulated kill is not retryable
                except Exception as e:  # noqa: BLE001 — degrade, not drop
                    err = e
                    _DISPATCH_ERRORS.inc()
                    if attempt < self.retry_limit and self.retry_backoff_s:
                        time.sleep(self.retry_backoff_s * (2 ** attempt))
            self.breaker.record_failure(
                error=f"{type(err).__name__}: {err}")
        try:
            faults.fire("match.fallback", backend=FALLBACK_BACKEND,
                        instance=self.instance_id)
            result = self._fallback_for(active).match_batch(
                batch.columns, batch.text_fields, n)
            _FALLBACK_BATCHES.inc()
            return result
        except InjectedCrash:
            raise
        except Exception as e:  # noqa: BLE001 — deterministic failure
            raise BatchMatchError(
                f"batch failed on primary ({self.backend}) and fallback "
                f"({FALLBACK_BACKEND}) lanes: {type(e).__name__}: {e}") from e

    def _fallback_for(self, active: _Active) -> FusedMatcher:
        """The degraded lane's matcher, built lazily per active version
        (off the happy path — most processes never pay for it)."""
        if active.fallback is None:
            with self._swap_lock:
                if active.fallback is None:
                    active.fallback = FusedMatcher(
                        active.bundle, backend=FALLBACK_BACKEND,
                        block_n=self.block_n)
        return active.fallback

    def finalize(self, pending: PendingBatch) -> RecordBatch:
        """Materialize a pending batch: single D2H transfer, attach the
        enrichment columns, apply filter mode, account stats."""
        t0 = time.perf_counter()
        faults.fire("match.d2h", version=pending.version_id)
        bm, matched = pending.result.to_host()
        out = pending.batch.with_column(ENRICH_COLUMN, bm)
        out = out.with_column(
            ENGINE_VERSION_COLUMN,
            np.full(pending.n, pending.version_id, np.int32))
        if self.mode == "filter":
            out = out.select(matched)
        with self._lock:
            self.stats.records_in += pending.n
            self.stats.records_out += len(out)
            self.stats.records_matched += int(matched.sum())
            self.stats.batches += 1
            self.stats.match_seconds += time.perf_counter() - t0
        return out

    def _match_per_field(self, active: _Active, batch: RecordBatch):
        """Fallback for backends without a fused dispatch (dfa_selective,
        shift_or): per-field engine calls, OR-reduced on device when every
        engine returns device arrays (one D2H at finalize), on host
        otherwise."""
        bms = [active.matchers[f].match(batch.columns[c])
               for f, c in match_pairs(tuple(active.matchers),
                                       batch.text_fields)]
        n, W = len(batch), active.bundle.words
        if not bms:
            return MatchResult(np.zeros((n, W), np.uint32),
                               np.zeros(n, bool))
        if any(isinstance(b, np.ndarray) for b in bms):
            bm = np.zeros((n, W), np.uint32)
            for b in bms:
                bm |= np.asarray(b)
            return MatchResult(bm, enrichment.any_match(bm))
        bm = functools.reduce(operator.or_, bms)
        return MatchResult(bm, (bm != 0).any(axis=1))

    # -- control topology --------------------------------------------------
    def poll_updates(self) -> int:
        """Consume update notifications; fetch+validate+swap.  Returns the
        number of successful swaps performed (paper §3.4.2 steps 4-6)."""
        if self.bus is None or self.store is None:
            return 0
        group = f"matcher/{self.instance_id}"
        swaps = 0
        t0 = time.perf_counter()
        with telemetry.span("match/poll_updates", cat="control",
                            instance=self.instance_id):
            swaps = self._poll_updates(group)
        _POLL_HIST.observe(time.perf_counter() - t0)
        return swaps

    def _poll_updates(self, group: str) -> int:
        swaps = 0
        for msg in self.bus.poll(MATCHER_UPDATES, group):
            ok = False
            err = ""
            try:
                ref = ObjectRef.from_dict(msg.value["object_ref"])
                expect_version = msg.value["engine_version"]
                expect_checksum = msg.value["checksum"]
                data = self.store.get(ref, verify=True)           # sha256
                bundle = EngineBundle.deserialize(data, verify=True)
                if bundle.version != expect_version:
                    raise ValueError(
                        f"version mismatch: got {bundle.version}, "
                        f"expected {expect_version}")
                if bundle.checksum() != expect_checksum:
                    raise ValueError("bundle checksum != notification checksum")
                self.swap(bundle)
                swaps += 1
                ok = True
            except Exception as e:  # noqa: BLE001 — ack failure, keep serving
                err = str(e)
            self.bus.commit(MATCHER_UPDATES, group, msg.offset)
            ack = {"instance": self.instance_id,
                   "engine_version": msg.value.get("engine_version"),
                   "ok": ok}
            if not ok:
                ack["error"] = err
                # echo the artifact reference so operators can tell WHICH
                # object failed fetch/validation from the ack alone
                ack["object_ref"] = msg.value.get("object_ref")
            self.bus.publish(MATCHER_ACKS, ack)
        return swaps

    def swap(self, bundle: EngineBundle) -> None:
        """Hot swap: build matchers off-path, then flip the reference."""
        with self._swap_lock:
            vid = self._active.version_id + 1
            self._install(bundle, version_id=vid)
            with self._lock:
                self.stats.swaps += 1

    # -- introspection -------------------------------------------------------
    @property
    def active_version(self) -> str:
        return self._active.bundle.version

    @property
    def active_version_id(self) -> int:
        return self._active.version_id

    @property
    def num_rules(self) -> int:
        return self._active.bundle.num_rules

    def _install(self, bundle: EngineBundle, version_id: int) -> None:
        matchers = build_matchers(bundle, backend=self.backend,
                                  block_n=self.block_n,
                                  confirm_backend=self.confirm_backend)
        fused = None
        if self.backend in FUSED_BACKENDS:
            fused = FusedMatcher(bundle, backend=self.backend,
                                 block_n=self.block_n)
        idents = (ruleset_idents(bundle.ruleset()) if bundle.ruleset_json
                  else {})
        self.version_rules[version_id] = idents
        self._active = _Active(bundle=bundle, matchers=matchers, fused=fused,
                               version_id=version_id,
                               activated_at=time.time())
        self.stats.versions[bundle.version] = self._active.activated_at
