"""MatchEngine — the executable multi-pattern matcher (paper §3.3).

Wraps a compiled automaton (``core.automaton.CompiledEngine``) with device
arrays and a jitted single-pass dispatch.  Engine *backends* select the
TPU-native algorithm (DESIGN.md §2):

    dfa        AC-DFA batch scan (Pallas kernel; CPU interpreter only)
    dfa_ref    pure-jnp form of the same — the default, and the lane that
               runs on the TPU
    shift_or   bit-parallel shift-AND (literals <= 32 B; Pallas kernel,
               CPU interpreter only) — beyond-paper
    parallel   associative-scan DFA (small automata) — beyond-paper

Mosaic does not compile the ``dfa`` and ``shift_or`` kernels for TPU (see
``TPU_REFUSED``), so those lanes raise when a matcher is built on a
compiled backend rather than failing batch by batch behind the breaker.

An ``EngineBundle`` groups one engine per record text field (paper §6.1 runs
"one Pattern Matching Engine instance per text field") plus version metadata;
it is the serializable artifact the Updater ships through the object store.
Because table shapes are bucketed (automaton.py), swapping a new bundle into
a running matcher re-uses every jit cache entry — the hot swap is O(bytes).

``FusedMatcher`` is the bundle-level fused dispatcher the enrich hot path
uses: all matched text columns of a batch go to the device in ONE dispatch,
the per-field bitmaps are OR-reduced and the any-match mask computed on
device, and the pair comes back in a single D2H transfer
(``MatchResult.to_host``).  Per-field ``MatchEngine.match`` remains for
tests, the selective/shift_or fallbacks, and the backfill plane.
"""
from __future__ import annotations

import hashlib
import io
import json
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.automaton import CompiledEngine, compile_rules, words_for_rules
from repro.core.patterns import RuleSet
from repro.device import on_cpu
from repro.kernels.dfa_scan.ops import (dfa_scan, dfa_scan_fused,
                                        dfa_scan_selective, pack_delta_any)
from repro.kernels.shift_or import ops as shift_or_ops

BACKENDS = ("dfa", "dfa_ref", "dfa_selective", "shift_or", "parallel")
# backends whose whole multi-field match can run as one fused device dispatch
FUSED_BACKENDS = ("dfa", "dfa_ref", "parallel")
# lanes whose Pallas kernel Mosaic refuses for TPU, with the compiler's words
# (tests/test_tpu_compile.py keeps the record current)
TPU_REFUSED = {
    "dfa": "dfa_scan_fused_kernel: Mosaic lowers a gather only within one "
           "vreg ('Only 2D gather is supported'; as a 2-D take_along_axis: "
           "'Multiple source vregs along gather dimension'), and the DFA "
           "table lookups span thousands",
    "shift_or": "shift_or_kernel: its (256, Wb) table row gather does not "
                "lower ('Shape mismatch in input, indices and output'; as a "
                "2-D take_along_axis: 'Multiple source vregs along gather "
                "dimension')",
}


def check_lane(backend: str) -> None:
    """Raise when ``backend`` needs a Pallas kernel that the current
    backend cannot compile (everything but the CPU interpreter, for the
    lanes in ``TPU_REFUSED``)."""
    if backend in TPU_REFUSED and not on_cpu():
        raise NotImplementedError(
            f"match backend {backend!r} cannot run on "
            f"{jax.default_backend()!r}: {TPU_REFUSED[backend]}. "
            f"Use backend='dfa_ref'.")

# -- device->host accounting -------------------------------------------------
# The enrich path must perform exactly ONE D2H transfer per batch; tests
# assert this via ``transfer_count`` (now an alias over the process-wide
# telemetry registry — deltas, which is what the tests take, are unchanged).
_D2H = telemetry.counter(
    "fluxsieve_match_d2h_total",
    help="Device-to-host transfers on the match plane (one per batch).")
_DISPATCH = telemetry.counter(
    "fluxsieve_match_dispatch_total",
    help="Fused device dispatches on the match plane.")
_MATCH_RECORDS = telemetry.counter(
    "fluxsieve_match_records_total",
    help="Records pushed through the fused match path.")


def transfer_count() -> int:
    return int(_D2H.value)


def _to_host(x):
    _D2H.inc()
    return jax.device_get(x)


class MatchResult:
    """Deferred match result: packed bitmap + any-match mask.

    Both stay on device (JAX async dispatch keeps computing behind it) until
    ``to_host`` materializes them in a single counted D2H transfer.  Results
    produced by host-side backends (dfa_selective) carry numpy arrays and
    transfer nothing."""

    __slots__ = ("_bm", "_mask", "_host")

    def __init__(self, bm, mask):
        self._bm = bm
        self._mask = mask
        self._host = isinstance(bm, np.ndarray)

    @property
    def on_device(self) -> bool:
        """True while the result still lives on device (work may be in
        flight); host-backend results were never dispatched."""
        return not self._host

    def to_host(self):
        """-> (bitmap (N, W) uint32, any_match (N,) bool), numpy."""
        if not self._host:
            self._bm, self._mask = _to_host((self._bm, self._mask))
            self._host = True
        return self._bm, self._mask


class MatchEngine:
    """One compiled automaton, resident on device, with stable jit shapes."""

    def __init__(self, engine: CompiledEngine, *, backend: str = "dfa_ref",
                 ruleset: RuleSet = None, block_n: int = 256,
                 confirm_backend: str = "ref"):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        check_lane(backend)
        if backend == "dfa_selective" and confirm_backend == "pallas":
            check_lane("dfa")
        self.backend = backend
        self.block_n = block_n
        self.confirm_backend = confirm_backend   # dfa_selective pass-2 engine
        self.engine = engine
        self.version = engine.version
        self.num_rules = engine.num_rules
        self.field = engine.field
        self._delta = jnp.asarray(engine.delta)
        self._emit = jnp.asarray(engine.emit)
        self._classes = jnp.asarray(engine.byte_classes)
        self._delta2 = None
        if backend == "dfa_selective":
            # Hyperscan-style confirm path (§Perf hillclimb D): packed
            # any-accept transition table for the prefilter pass
            self._delta2 = pack_delta_any(engine.delta, engine.emit)
        self._shift_or = None
        if backend == "shift_or":
            if ruleset is None:
                raise ValueError("shift_or backend needs the RuleSet to pack literals")
            self._shift_or = shift_or_ops.compile_shift_or(ruleset, engine.field)

    @property
    def words(self) -> int:
        return self.engine.words

    def match(self, data) -> jnp.ndarray:
        """data: (N, L) uint8 -> (N, W) uint32 packed rule bitmaps."""
        if self.backend == "dfa_selective":
            return dfa_scan_selective(data, self.engine.delta,
                                      self.engine.emit,
                                      self.engine.byte_classes,
                                      delta2=self._delta2,
                                      backend=self.confirm_backend,
                                      block_n=self.block_n)
        data = jnp.asarray(data)
        if self.backend == "shift_or":
            bm = shift_or_ops.shift_or_match(data, self._shift_or,
                                             backend="pallas",
                                             block_n=self.block_n)
            # shift_or packs exactly ceil(rules/32) words; widen to the bucket
            W = self.words
            if bm.shape[1] < W:
                bm = jnp.pad(bm, ((0, 0), (0, W - bm.shape[1])))
            return bm
        backend = {"dfa": "pallas", "dfa_ref": "ref", "parallel": "parallel"}[self.backend]
        return dfa_scan(data, self._delta, self._emit, self._classes,
                        backend=backend, block_n=self.block_n)


@dataclass(frozen=True)
class EngineBundle:
    """Versioned set of per-field compiled engines (the deployable artifact)."""
    version: str
    num_rules: int
    engines: dict            # field -> CompiledEngine
    ruleset_json: str = ""   # carried so shift_or backends can re-pack literals

    @property
    def fields(self) -> tuple:
        return tuple(sorted(self.engines))

    @property
    def words(self) -> int:
        return words_for_rules(self.num_rules)

    def checksum(self) -> str:
        h = hashlib.sha256()
        h.update(self.version.encode())
        h.update(str(self.num_rules).encode())
        for f in self.fields:
            h.update(f.encode())
            h.update(self.engines[f].checksum().encode())
        h.update(self.ruleset_json.encode())
        return h.hexdigest()

    def serialize(self) -> bytes:
        arrays = {}
        for f, eng in self.engines.items():
            arrays[f"eng_{f}"] = np.frombuffer(eng.serialize(), np.uint8)
        manifest = json.dumps({
            "version": self.version, "num_rules": self.num_rules,
            "fields": list(self.fields), "checksum": self.checksum(),
            "ruleset_json": self.ruleset_json,
        })
        buf = io.BytesIO()
        np.savez_compressed(buf, manifest=np.array(manifest), **arrays)
        return buf.getvalue()

    @staticmethod
    def deserialize(data: bytes, verify: bool = True) -> "EngineBundle":
        try:
            z = np.load(io.BytesIO(data), allow_pickle=False)
            manifest = json.loads(str(z["manifest"]))
            engines = {f: CompiledEngine.deserialize(z[f"eng_{f}"].tobytes(),
                                                     verify=verify)
                       for f in manifest["fields"]}
        except ValueError:
            raise
        except Exception as e:  # container damage (zlib/zip/json errors)
            raise ValueError(f"corrupt bundle artifact: {e}") from e
        bundle = EngineBundle(version=manifest["version"],
                              num_rules=manifest["num_rules"], engines=engines,
                              ruleset_json=manifest.get("ruleset_json", ""))
        if verify and manifest["checksum"] != bundle.checksum():
            raise ValueError("bundle checksum mismatch — corrupt artifact")
        return bundle

    def ruleset(self) -> RuleSet:
        return RuleSet.from_json(self.ruleset_json)


def compile_bundle(ruleset: RuleSet, fields) -> EngineBundle:
    """Compile one engine per text field (rules select their fields)."""
    engines = {f: compile_rules(ruleset, f) for f in fields}
    return EngineBundle(version=ruleset.version_hash(),
                        num_rules=ruleset.num_rules, engines=engines,
                        ruleset_json=ruleset.to_json())


def build_matchers(bundle: EngineBundle, *, backend: str = "dfa_ref",
                   block_n: int = 256, confirm_backend: str = "ref") -> dict:
    """field -> MatchEngine, ready for StreamProcessor hot-swap."""
    rs = bundle.ruleset() if bundle.ruleset_json else None
    return {f: MatchEngine(bundle.engines[f], backend=backend, ruleset=rs,
                           block_n=block_n, confirm_backend=confirm_backend)
            for f in bundle.fields}


def match_pairs(engine_fields, text_fields):
    """(engine_field, column) routing shared by the fused plan and the
    per-field fallback: a '*' engine applies to every text column, a named
    engine only to its own column (and only when the batch carries it)."""
    for fieldname in engine_fields:
        if fieldname == "*":
            for c in text_fields:
                yield fieldname, c
        elif fieldname in text_fields:
            yield fieldname, fieldname


@dataclass(frozen=True)
class _FusedPlan:
    """Stacked device tables for one batch schema.  Engines shared across
    columns (a '*' engine) are stored once; ``eng_idx`` maps each stacked
    field slot to its table row."""
    cols: tuple              # column names, one per stacked field slot
    eng_idx: tuple           # per-slot row into the unique-engine tables
    luts: object             # (E, 256) int32
    deltas: object           # (E, S, C) int32
    emits: object            # (E, S, W) uint32


class FusedMatcher:
    """EngineBundle-level fused dispatcher: one device dispatch per batch.

    All matched text columns are stacked into one ``(F, N, L)`` input; the
    per-field tables are padded to a common shape bucket and stacked once
    per batch schema (cached per text-field tuple, so hot-swapping a new
    bundle re-uses every jit cache entry exactly like the per-field path).
    The scan, the OR across fields, and the any-match mask all run on
    device; ``MatchResult.to_host`` is the single D2H.
    """

    def __init__(self, bundle: EngineBundle, *, backend: str = "dfa_ref",
                 block_n: int = 256):
        if backend not in FUSED_BACKENDS:
            raise ValueError(f"backend {backend!r} has no fused dispatch "
                             f"(supported: {FUSED_BACKENDS})")
        check_lane(backend)
        self.bundle = bundle
        self.backend = backend
        self.block_n = block_n
        self.words = bundle.words
        self._kernel = {"dfa": "pallas", "dfa_ref": "ref",
                        "parallel": "parallel"}[backend]
        self._plans: dict = {}

    def _plan(self, text_fields: tuple) -> _FusedPlan:
        plan = self._plans.get(text_fields)
        if plan is None:
            plan = self._build_plan(text_fields)
            self._plans[text_fields] = plan
        return plan

    def _build_plan(self, text_fields: tuple) -> _FusedPlan:
        pairs = [(c, self.bundle.engines[f])         # (column, CompiledEngine)
                 for f, c in match_pairs(self.bundle.fields, text_fields)]
        if not pairs:
            return _FusedPlan(cols=(), eng_idx=(), luts=None, deltas=None,
                              emits=None)
        uniq, eng_idx, slot = [], [], {}
        for _, e in pairs:
            if id(e) not in slot:
                slot[id(e)] = len(uniq)
                uniq.append(e)
            eng_idx.append(slot[id(e)])
        E = len(uniq)
        S = max(e.bucket for e in uniq)
        C = max(e.n_classes for e in uniq)
        W = self.words
        luts = np.zeros((E, 256), np.int32)
        deltas = np.zeros((E, S, C), np.int32)      # padded rows unreachable
        emits = np.zeros((E, S, W), np.uint32)
        for i, e in enumerate(uniq):
            luts[i] = e.byte_classes
            deltas[i, :e.bucket, :e.n_classes] = e.delta
            emits[i, :e.bucket] = e.emit
        return _FusedPlan(cols=tuple(c for c, _ in pairs),
                          eng_idx=tuple(eng_idx),
                          luts=jnp.asarray(luts), deltas=jnp.asarray(deltas),
                          emits=jnp.asarray(emits))

    def match_batch(self, columns: dict, text_fields, n: int) -> MatchResult:
        """columns: name -> (N, L) uint8; -> deferred (bitmap, mask)."""
        plan = self._plan(tuple(text_fields))
        if not plan.cols:
            return MatchResult(np.zeros((n, self.words), np.uint32),
                               np.zeros(n, bool))
        L = max(columns[c].shape[1] for c in plan.cols)
        mats = []
        for c in plan.cols:
            m = columns[c]
            if m.shape[1] < L:
                m = np.pad(np.asarray(m), ((0, 0), (0, L - m.shape[1])))
            mats.append(np.asarray(m))
        data = np.stack(mats)                       # (F, N, L): one H2D
        with telemetry.span("match/dispatch", cat="match", n=int(n),
                            fields=len(plan.cols)):
            bm, mask = dfa_scan_fused(data, plan.luts, plan.deltas,
                                      plan.emits, eng_idx=plan.eng_idx,
                                      backend=self._kernel,
                                      block_n=self.block_n)
        _DISPATCH.inc()
        _MATCH_RECORDS.inc(int(n))
        return MatchResult(bm, mask)
