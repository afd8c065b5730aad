"""Pallas TPU kernel: fused enrichment-bitmap predicate + count.

The analytical-plane fast path (paper §3.1 "Query Mapper ... bypass expensive
full-table scans"): AND each record's packed rule bitmap with the query mask,
reduce-any per record, and accumulate per-block match counts — one pass over
the enrichment column, no string data touched.  Memory-bound by design; the
roofline term is column bytes / HBM bandwidth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.device import on_cpu

BLOCK_N = 1024


def _kernel(bm_ref, q_ref, match_ref, count_ref):
    hit = (bm_ref[...] & q_ref[...]) != 0                       # (blk, W)
    any_hit = jnp.any(hit, axis=1)
    match_ref[...] = any_hit.astype(jnp.int32)
    # the block count fills one lane-wide row (a (1, 1) block breaks the
    # 8x128 tiling rule); the wrapper keeps lane 0
    count_ref[...] = jnp.full(count_ref.shape,
                              jnp.sum(any_hit.astype(jnp.int32)), jnp.int32)


def _query_kernel(bm_ref, q_ref, match_ref):
    # conjunctive multi-mask predicate: AND over the P masks of "any bit in
    # common".  P is static, so the loop unrolls into 2-D VPU ops (no 3-D
    # broadcast — friendlier to the TPU lowering than a (blk, P, W) tensor).
    bm = bm_ref[...]                                         # (blk, W)
    ok = None
    for p in range(q_ref.shape[0]):
        hit_p = jnp.any((bm & q_ref[p][None, :]) != 0, axis=1)
        ok = hit_p if ok is None else (ok & hit_p)
    match_ref[...] = ok.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n",))
def bitmap_query_kernel(bitmaps, masks, *, block_n: int = BLOCK_N):
    """bitmaps: (N, W) uint32 (N % block_n == 0); masks: (P, W) uint32.
    Returns match (N,) int32 — 1 where the record satisfies EVERY mask
    (AND across predicates, any-bit within each).  One grid pass over the
    stacked enrichment column; the multi-segment query executor feeds all
    bitmap-scan segments of a query through this in a single dispatch."""
    N, W = bitmaps.shape
    P = masks.shape[0]
    assert N % block_n == 0
    grid = (N // block_n,)
    return pl.pallas_call(
        _query_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, W), lambda i: (i, 0)),
            pl.BlockSpec((P, W), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), jnp.int32),
        interpret=on_cpu(),
    )(bitmaps, masks)


def _word_query_kernel(cols_ref, bits_ref, match_ref):
    hit = (cols_ref[...] & bits_ref[...]) != 0               # (blk, P)
    match_ref[...] = jnp.all(hit, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n",))
def bitmap_word_query_kernel(cols, bits, *, block_n: int = BLOCK_N):
    """cols: (N, P) uint32 pre-gathered bitmap word columns (N % block_n
    == 0); bits: (P,) uint32 single-word masks.  Returns match (N,) int32 —
    the word-sliced fast path of ``bitmap_query_kernel``: the executor
    gathers only the words a query touches, so HBM traffic is N*P words
    instead of N*W."""
    N, P = cols.shape
    assert N % block_n == 0
    grid = (N // block_n,)
    return pl.pallas_call(
        _word_query_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, P), lambda i: (i, 0)),
            pl.BlockSpec((1, P), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), jnp.int32),
        interpret=on_cpu(),
    )(cols, bits[None])


@functools.partial(jax.jit, static_argnames=("block_n",))
def bitmap_filter_kernel(bitmaps, query, *, block_n: int = BLOCK_N):
    """bitmaps: (N, W) uint32 (N % block_n == 0); query: (1, W) uint32.
    Returns (match (N,) int32, block_counts (N//block_n, 1) int32)."""
    N, W = bitmaps.shape
    assert N % block_n == 0
    grid = (N // block_n,)
    match, counts = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, W), lambda i: (i, 0)),
            pl.BlockSpec((1, W), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((grid[0], 1, 128), jnp.int32),
        ],
        interpret=on_cpu(),
    )(bitmaps, query)
    return match, counts[:, :, 0]
