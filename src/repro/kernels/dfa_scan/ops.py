"""Jitted wrappers for the DFA-scan kernels: padding, batch-size (N)
bucketing, backend selection, and retrace accounting so hot-swapped engines
AND ragged tail batches never retrace.

``dfa_scan`` is the single-field entry (tests, backfill, selective confirm);
``dfa_scan_fused`` is the multi-field entry used by ``matcher.FusedMatcher``
— one device dispatch for all fields, per-field bitmaps OR-reduced and the
any-match mask computed on device, nothing transferred to host.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dfa_scan.dfa_scan import dfa_scan_fused_kernel, BLOCK_N
from repro.kernels.dfa_scan.ref import dfa_scan_fused_ref

# (fn, backend) -> number of jit traces.  Incremented at TRACE time (a
# python side effect inside the jitted function), so tests can assert that
# varying batch sizes after warmup trigger no new retraces.
TRACE_COUNTS: collections.Counter = collections.Counter()


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def bucket_n(n: int, block_n: int = BLOCK_N) -> int:
    """Pad a batch size to a power of two at/above ``block_n`` (mirrors the
    S/C/W table bucketing in automaton.py): variable-size tail batches hit a
    handful of shape buckets instead of retracing the jit cache per distinct
    N."""
    n = max(n, 1)
    if n <= block_n:
        return block_n
    return _round_up(1 << (n - 1).bit_length(), block_n)


def _pad_rows(data, n_pad: int):
    """Zero-pad axis -2 (records) of a host or device array to n_pad."""
    n = data.shape[-2]
    if n_pad == n:
        return data
    widths = [(0, 0)] * data.ndim
    widths[-2] = (0, n_pad - n)
    if isinstance(data, np.ndarray):
        return np.pad(data, widths)
    return jnp.pad(data, widths)


@functools.partial(jax.jit, static_argnames=("eng_idx", "backend", "block_n"))
def _dispatch_fused(data, luts, deltas, emits, *, eng_idx: tuple,
                    backend: str, block_n: int):
    TRACE_COUNTS[("dfa_scan", backend)] += 1
    if backend == "pallas":
        bm = dfa_scan_fused_kernel(data, luts, deltas, emits,
                                   jnp.asarray(eng_idx, jnp.int32),
                                   block_n=block_n)
        return bm, (bm != 0).any(axis=1)
    if backend == "ref":
        bms = dfa_scan_fused_ref(data, luts, deltas, emits, eng_idx=eng_idx)
    elif backend == "parallel":
        eng = jnp.asarray(eng_idx, jnp.int32)
        cls = jnp.take(luts.reshape(-1),
                       eng[:, None, None] * 256 + data.astype(jnp.int32))
        bms = jax.vmap(_parallel_dfa)(cls, jnp.take(deltas, eng, axis=0),
                                      jnp.take(emits, eng, axis=0))
    else:
        raise ValueError(backend)
    bm = bms[0]
    for f in range(1, bms.shape[0]):                    # static F: unrolled OR
        bm = bm | bms[f]
    return bm, (bm != 0).any(axis=1)


def dfa_scan_fused(data, luts, deltas, emits, *, eng_idx: tuple = None,
                   backend: str = "ref", block_n: int = BLOCK_N):
    """data: (F, N, L) uint8 (any N); luts: (E, 256) int32; deltas:
    (E, S, C) int32; emits: (E, S, W) uint32; eng_idx: length-F tuple
    mapping each field slot to its table row (default identity — engines
    shared across columns need only one table copy).  Returns the pair
    ``(bitmap (N, W) uint32, any_match (N,) bool)`` — the OR of all
    per-field bitmaps — as DEVICE arrays (the caller owns the single D2H)."""
    F, N = data.shape[0], data.shape[1]
    if eng_idx is None:
        eng_idx = tuple(range(F))
    data = _pad_rows(data, bucket_n(N, block_n))
    bm, mask = _dispatch_fused(data, luts, deltas, emits,
                               eng_idx=tuple(eng_idx), backend=backend,
                               block_n=block_n)
    return bm[:N], mask[:N]


def dfa_scan(data, delta, emit, byte_classes, *, backend: str = "ref",
             block_n: int = BLOCK_N):
    """data: (N, L) uint8 (any N) -> (N, W) uint32 rule bitmaps."""
    bm, _ = dfa_scan_fused(data[None], byte_classes[None], delta[None],
                           emit[None], backend=backend, block_n=block_n)
    return bm


# ---------------------------------------------------------------------------
# Selective two-pass scan (§Perf hillclimb D): Hyperscan-style confirm path.
# Pass 1 runs the DFA tracking ONE bit per record ("did any accepting state
# occur"), with the accept flag PACKED into the transition value
# (delta2 = next_state*2 + accepts(next_state)) so each byte costs a single
# gather + shift/and/or.  Pass 2 (the full emit-bitmap walk) runs only on
# the records that matched — under the paper's high-selectivity workloads,
# almost none.  Tables are int16 when the packed value fits (S*2 < 32768),
# halving the working set.
# ---------------------------------------------------------------------------

def pack_delta_any(delta, emit):
    """(S, C) int32 + (S, W) emit -> packed delta2 (int16 when it fits)."""
    import numpy as onp
    d = onp.asarray(delta)
    accepts = (onp.asarray(emit) != 0).any(axis=1).astype(onp.int32)
    packed = d * 2 + accepts[d]
    if packed.max() < 32768:
        return packed.astype(onp.int16)
    return packed


@functools.partial(jax.jit)
def _any_scan(cls, delta2_flat, n_classes):
    """cls: (N, L) int32 class ids -> (N,) bool any-accept flag."""
    TRACE_COUNTS[("any_scan", "ref")] += 1
    N, L = cls.shape

    def body(carry, col):
        packed, hit = carry
        state = (packed >> 1).astype(jnp.int32)
        nxt = jnp.take(delta2_flat, state * n_classes + col).astype(jnp.int32)
        return (nxt, hit | (nxt & 1).astype(jnp.bool_)), None

    init = (jnp.zeros((N,), jnp.int32), jnp.zeros((N,), jnp.bool_))
    (_, hit), _ = jax.lax.scan(body, init, cls.T)
    return hit


def dfa_scan_selective(data, delta, emit, byte_classes, delta2=None, *,
                       backend: str = "ref", block_n: int = BLOCK_N):
    """Two-pass matcher: any-accept prefilter + full confirm on matches.
    data: (N, L) uint8 -> (N, W) uint32 (numpy).  Not jittable end-to-end
    (the confirm subset is data-dependent); both passes bucket their batch
    dimension so neither retraces as N varies.  ``backend``/``block_n``
    select the confirm-pass engine (threaded through from the configuring
    MatchEngine rather than hardcoding the jnp oracle)."""
    import numpy as onp
    if delta2 is None:
        delta2 = pack_delta_any(delta, emit)
    N = data.shape[0]
    padded = _pad_rows(data, bucket_n(N, block_n))
    cls = jnp.take(jnp.asarray(byte_classes),
                   jnp.asarray(padded).astype(jnp.int32))
    n_classes = delta.shape[1]
    hit = onp.asarray(_any_scan(cls, jnp.asarray(delta2).reshape(-1),
                                n_classes))[:N]
    W = emit.shape[1]
    out = onp.zeros((N, W), onp.uint32)
    idx = onp.flatnonzero(hit)
    if len(idx) == 0:
        return out
    sub = onp.asarray(data)[idx]              # confirm pass buckets internally
    bm = dfa_scan(sub, delta, emit, byte_classes, backend=backend,
                  block_n=block_n)
    out[idx] = onp.asarray(bm)
    return out


def _parallel_dfa(cls, delta, emit):
    """Beyond-paper variant: Mytkowicz-style data-parallel FSM.

    Each byte position induces a transition *function* [S]->[S] (a gathered
    column of delta); function composition is associative, so the running
    state at every position is an ``associative_scan`` — O(log L) depth at
    the cost of materializing (N, L, S) function tables.  Only sensible for
    small automata (S <= 256); the roofline trade is analyzed in
    EXPERIMENTS.md §Perf.
    """
    N, L = cls.shape
    S = delta.shape[0]
    if S > 256:
        raise ValueError("parallel_dfa is intended for small automata (S<=256)")
    # funcs[n, l, s] = delta[s, cls[n, l]]
    funcs = delta.T[cls]                                        # (N, L, S)

    def compose(f, g):
        # (f then g): h[s] = g[f[s]]
        return jnp.take_along_axis(g, f, axis=-1)

    prefix = jax.lax.associative_scan(compose, funcs, axis=1)   # (N, L, S)
    states = prefix[..., 0]                                     # start state 0
    bms = jnp.take(emit, states, axis=0)                        # (N, L, W)
    return jax.lax.reduce_or(bms, axes=(1,))
