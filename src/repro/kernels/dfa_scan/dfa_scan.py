"""Pallas TPU kernel: batched Aho-Corasick DFA scan, fused across fields.

Layout: the grid is ``(N // block_n, F)`` — the major axis tiles the record
batch, the minor (fastest-varying) **field axis** sweeps the per-field
automata while the SAME output block stays resident in VMEM, OR-accumulating
each field's rule bitmap.  F text fields therefore cost one kernel launch
and one (block_n, W) output write per record tile (the fused multi-field
dispatch's device half; matcher.FusedMatcher is the host half).  Engines
shared by several field slots are stored once: the slot -> table-row map
``eng_idx`` is a scalar-prefetch operand that the table BlockSpecs index.

The byte->class LUT is folded into the kernel: the input tile is the RAW
``(block_n, L) uint8`` bytes and each field's 256-entry LUT rides along in
VMEM.  Byte positions are read 128 at a time as one lane-aligned tile,
transposed into a VMEM scratch so each position is a row at a dynamic
sublane offset.  Transition tables are int16 whenever the padded automaton
fits (S < 32768), halving the delta block.

VMEM budget per grid step (defaults, 1000-rule engine):
    byte tile   256 x 512 x 1 B  = 0.125 MiB  (uint8; LUT applied in-kernel)
    lut         256 x 4 B        = 1 KiB
    delta       4096 x 64 x 2 B  = 0.5 MiB    (alphabet-compressed, int16)
    emit        4096 x 32 x 4 B  = 0.5 MiB
    scratch     128 x 256 x 4 B  = 0.125 MiB
well under the ~16 MiB v5e VMEM.

Mosaic does not lower this kernel for TPU: it lowers a gather only within
one vreg along the gathered axis, and the three per-byte table lookups
(LUT, delta, emit rows) span many.  ``tests/test_tpu_compile.py`` records
the refusal; ``core.matcher`` refuses the ``dfa`` lane on a compiled
backend instead of letting it fail batch by batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import on_cpu

BLOCK_N = 256
LANES = 128       # byte positions per aligned input tile


def _kernel(eng_ref, data_ref, lut_ref, delta_ref, emit_ref, out_ref,
            cols_ref):
    del eng_ref                                          # used by the specs
    _, blk_n, L = data_ref.shape
    _, S, C = delta_ref.shape
    W = emit_ref.shape[2]
    f = pl.program_id(1)
    lut = lut_ref[0, 0]                                  # (256,) int32
    delta_flat = delta_ref[0].reshape(S * C)             # int16 when S < 2^15
    emit = emit_ref[0]                                   # (S, W) uint32

    def step(j, carry):
        state, bm = carry
        byte = cols_ref[pl.ds(j, 1), :][0]                       # (blk_n,)
        col = jnp.take(lut, byte)                                # LUT gather
        state = jnp.take(delta_flat, state * C + col)            # per-lane gather
        state = state.astype(jnp.int32)
        bm = bm | jnp.take(emit, state, axis=0)                  # row gather
        return state, bm

    def chunk(c, carry):
        off = pl.multiple_of(c * LANES, LANES)
        cols_ref[...] = data_ref[0, :, pl.ds(off, LANES)].astype(jnp.int32).T
        return jax.lax.fori_loop(0, LANES, step, carry)

    state0 = jnp.zeros((blk_n,), jnp.int32)
    bm0 = jnp.zeros((blk_n, W), jnp.uint32)
    _, bm = jax.lax.fori_loop(0, L // LANES, chunk, (state0, bm0))

    # OR-accumulate across the field axis: the out block is revisited on
    # consecutive grid steps (f is the minor grid axis), so it stays in VMEM.
    @pl.when(f == 0)
    def _():
        out_ref[...] = bm

    @pl.when(f != 0)
    def _():
        out_ref[...] = out_ref[...] | bm


@functools.partial(jax.jit, static_argnames=("block_n",))
def dfa_scan_fused_kernel(data, luts, deltas, emits, eng_idx, *,
                          block_n: int = BLOCK_N):
    """data: (F, N, L) uint8 raw bytes (N % block_n == 0);
    luts: (E, 256) int32 byte->class; deltas: (E, S, C) int; emits:
    (E, S, W) uint32; eng_idx: (F,) int32 mapping each field slot to its
    table row.  -> (N, W) uint32, the OR of all per-field bitmaps.

    L is zero-padded to a multiple of 128: byte 0 never occurs in a
    pattern, so trailing zeros only walk fail links."""
    F, N, L = data.shape
    E, S, C = deltas.shape
    W = emits.shape[2]
    assert N % block_n == 0, (N, block_n)
    assert eng_idx.shape == (F,), (eng_idx.shape, F)
    if L % LANES:
        data = jnp.pad(data, ((0, 0), (0, 0), (0, LANES - L % LANES)))
        L = data.shape[2]
    if S < 2 ** 15:
        deltas = deltas.astype(jnp.int16)    # halve the VMEM delta block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // block_n, F),
        in_specs=[
            pl.BlockSpec((1, block_n, L), lambda i, f, eng: (f, i, 0)),
            pl.BlockSpec((1, 1, 256), lambda i, f, eng: (eng[f], 0, 0)),
            pl.BlockSpec((1, S, C), lambda i, f, eng: (eng[f], 0, 0)),
            pl.BlockSpec((1, S, W), lambda i, f, eng: (eng[f], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, W), lambda i, f, eng: (i, 0)),
        scratch_shapes=[pltpu.VMEM((LANES, block_n), jnp.int32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, W), jnp.uint32),
        interpret=on_cpu(),
    )(eng_idx, data, luts.reshape(E, 1, 256), deltas, emits)
