"""Jitted wrappers for bitmap filtering: count and copy (index-compaction)
query modes over enrichment columns, plus the multi-segment stacked entry
the query executor dispatches through.

``bitmap_query_stacked`` is the analytical-plane analogue of the ingest
side's ``dfa_scan_fused``: all bitmap-scan segments of one query are
concatenated on N (with a per-row segment-slot vector), matched against the
query's conjunctive mask set in ONE device dispatch, and per-segment match
counts are reduced on device by a one-hot contraction — the caller owns
the single D2H transfer.  Batch sizes bucket through
``dfa_scan.ops.bucket_n`` so ragged segment totals never retrace the jit
cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitmap_filter.bitmap_filter import (bitmap_filter_kernel,
                                                       bitmap_query_kernel,
                                                       bitmap_word_query_kernel,
                                                       BLOCK_N)
from repro.kernels.bitmap_filter.ref import (bitmap_filter_ref,
                                             bitmap_query_ref,
                                             bitmap_word_query_ref)
from repro.kernels.dfa_scan.ops import TRACE_COUNTS, bucket_n


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def bitmap_match(bitmaps, query, *, backend: str = "ref",
                 block_n: int = BLOCK_N):
    """(N, W) & (W,) -> match (N,) bool."""
    N = bitmaps.shape[0]
    if backend == "pallas":
        n_pad = _round_up(max(N, 1), block_n)
        bm = jnp.pad(bitmaps, ((0, n_pad - N), (0, 0)))
        match, _ = bitmap_filter_kernel(bm, query[None], block_n=block_n)
        return match[:N].astype(bool)
    return bitmap_filter_ref(bitmaps, query)


def bitmap_count(bitmaps, query, *, backend: str = "ref",
                 block_n: int = BLOCK_N):
    """Aggregation (count) query — paper's Q3/Qx-with-count."""
    if backend == "pallas":
        N = bitmaps.shape[0]
        n_pad = _round_up(max(N, 1), block_n)
        bm = jnp.pad(bitmaps, ((0, n_pad - N), (0, 0)))
        _, counts = bitmap_filter_kernel(bm, query[None], block_n=block_n)
        return counts.sum(dtype=jnp.int32)
    return bitmap_filter_ref(bitmaps, query).sum(dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_out",))
def bitmap_select(bitmaps, query, *, max_out: int):
    """Copy mode: compacted indices of matching records (static bound).
    Returns (indices (max_out,) int32 padded with -1, count).

    Compaction is a cumsum + scatter (stable, ascending ids) instead of a
    full argsort over N — O(N) work and int32 throughout."""
    match = bitmap_filter_ref(bitmaps, query)
    count = match.sum(dtype=jnp.int32)
    N = match.shape[0]
    pos = jnp.cumsum(match.astype(jnp.int32)) - 1            # dest per match
    dest = jnp.where(match & (pos < max_out), pos, max_out)  # max_out = drop
    idx = jnp.full((max_out,), -1, jnp.int32)
    idx = idx.at[dest].set(jnp.arange(N, dtype=jnp.int32), mode="drop")
    return idx, count


# ---------------------------------------------------------------------------
# Multi-segment stacked entry (query executor's single dispatch per query)
# ---------------------------------------------------------------------------

def _seg_bucket(s: int) -> int:
    """Pad the static segment count to a power of two so a growing store
    hits a handful of jit shape buckets, not one trace per segment count."""
    return 1 << (max(s, 1) - 1).bit_length()


# How ``_segment_counts`` lays out its one-hots, from the static slot count.
# Up to ``_ONE_HOT_MAX`` slots the match contracts against one (N, S)
# one-hot, which XLA strength-reduces to one fused select-reduce whose VPU
# work grows with S.  Above it the slot id splits as ``hi * _LO_SLOTS + lo``
# and the counts are an int8 (hi, N) x (N, lo) contraction on the MXU.  On a
# TPU v5e at 2**20 rows the select-reduce took 17, 34 and 89 us at 32, 64
# and 128 slots, the split contraction 49-54 us from 64 to 256 slots.
_ONE_HOT_MAX = 64
_LO_SLOTS = 16


def _segment_counts(match, row_seg, num_segments: int):
    """Exact per-segment match counts: ``counts[s] = sum(match & (row_seg
    == s))`` as int32, a dense one-hot contraction rather than a scatter
    (a scatter serialises on colliding slots, and every row of a segment
    collides).  ``num_segments`` is static and a power of two; rows whose
    slot lies outside ``[0, num_segments)`` count nowhere."""
    lo = num_segments if num_segments <= _ONE_HOT_MAX else _LO_SLOTS
    hi = num_segments // lo
    slots = functools.partial(jnp.arange, dtype=row_seg.dtype)
    hot_hi = (((row_seg // lo)[:, None] == slots(hi))
              & match[:, None]).astype(jnp.int8)
    hot_lo = ((row_seg % lo)[:, None] == slots(lo)).astype(jnp.int8)
    counts = jax.lax.dot_general(hot_hi, hot_lo, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)
    return counts.reshape(num_segments)


@functools.partial(jax.jit, static_argnames=("num_segments", "backend",
                                             "block_n"))
def _query_dispatch(bm, masks, row_seg, *, num_segments: int, backend: str,
                    block_n: int):
    TRACE_COUNTS[("bitmap_query", backend)] += 1
    if backend == "pallas":
        match = bitmap_query_kernel(bm, masks,
                                    block_n=block_n).astype(jnp.bool_)
    else:
        match = bitmap_query_ref(bm, masks)
    return match, _segment_counts(match, row_seg, num_segments)


def bitmap_query_stacked(bitmaps, masks, row_seg, *, num_segments: int,
                         backend: str = "ref", block_n: int = BLOCK_N):
    """bitmaps: (N, W) uint32 — the bitmap-scan segments of one query
    concatenated on N (any N; rows bucket via ``bucket_n``); masks:
    (P, W) uint32 conjunctive predicate masks; row_seg: (N,) int32 mapping
    each row to its segment slot.

    Returns DEVICE arrays ``(match, counts)`` — match over the concatenated
    rows plus per-segment match counts reduced on device — in PADDED form:
    match is ``(bucket_n(N),)`` bool and counts ``(pow2 >= num_segments,)``
    int32.  Zero-padded rows can never match (their bitmaps are empty) and
    padded segment slots stay zero, so callers slice ``[:N]`` /
    ``[:num_segments]`` on the HOST after the D2H transfer they own — the
    hot path stays one jitted dispatch with no eager device ops (an eager
    pad or slice costs more than the whole match at small N)."""
    N = bitmaps.shape[0]
    n_pad = bucket_n(N, block_n)
    if n_pad != N:
        bitmaps = jnp.pad(bitmaps, ((0, n_pad - N), (0, 0)))
        row_seg = jnp.pad(row_seg, (0, n_pad - N))
    return _query_dispatch(
        bitmaps, masks, row_seg, num_segments=_seg_bucket(num_segments),
        backend=backend, block_n=block_n)


@functools.partial(jax.jit, static_argnames=("num_segments", "backend",
                                             "block_n", "with_counts"))
def _word_query_dispatch(cols, bits, row_seg, *, num_segments: int,
                         backend: str, block_n: int, with_counts: bool):
    TRACE_COUNTS[("bitmap_query_words", backend)] += 1
    if backend == "pallas":
        match = bitmap_word_query_kernel(cols, bits,
                                         block_n=block_n).astype(jnp.bool_)
    else:
        match = bitmap_word_query_ref(cols, bits)
    if not with_counts:
        return match, None
    # bucket padding appends slot-0 rows after the last segment's run, so
    # row_seg need not be sorted; padded rows never match and add nothing
    return match, _segment_counts(match, row_seg, num_segments)


def bitmap_query_words(cols, bits, row_seg, *, num_segments: int,
                       backend: str = "ref", block_n: int = BLOCK_N,
                       with_counts: bool = True):
    """Word-sliced variant of ``bitmap_query_stacked`` — the executor's hot
    path.  cols: (N, P) uint32, the P bitmap WORD columns the query's
    single-rule predicates actually touch, pre-gathered at stack-build
    time; bits: (P,) uint32 single-word masks; row_seg: (N,) int32 segment
    slots.  Same padded device returns ``(match, counts)`` as the stacked
    entry (slice on the host after the D2H); memory traffic per query is
    N*P words instead of N*W.

    ``with_counts=False`` skips the device-side per-segment reduction and
    returns ``(match, None)`` — the right call on backends where the
    one-hot reduction costs more than transferring the mask and counting
    on the host (XLA CPU); on accelerators the reduction shrinks the D2H
    payload from N bytes to num_segments ints."""
    N = cols.shape[0]
    n_pad = bucket_n(N, block_n)
    if n_pad != N:
        cols = jnp.pad(cols, ((0, n_pad - N), (0, 0)))
        row_seg = jnp.pad(row_seg, (0, n_pad - N))
    return _word_query_dispatch(
        cols, bits, row_seg, num_segments=_seg_bucket(num_segments),
        backend=backend, block_n=block_n, with_counts=with_counts)
