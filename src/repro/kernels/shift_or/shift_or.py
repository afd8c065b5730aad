"""Pallas TPU kernel: bit-parallel shift-AND multi-pattern matcher.

Pure VPU workload: one (256, Wb) table row-gather plus shift/or/and per byte
position, advancing BLOCK_N records in lock-step.  Compared to dfa_scan this
trades automaton generality (literals <= 32 B only) for a state representation
that lives entirely in vector registers — the beyond-paper fast path for
short keyword rules (DESIGN.md §2).

VMEM per grid step: bytes tile 256x512 = 128 KiB (uint8->int32 widened
outside), table 256 x Wb x 4 B (Wb=320 for 1000 short patterns ~ 320 KiB),
states 2 x 256 x Wb x 4 B.

Mosaic does not lower this kernel for TPU: the per-byte row gather
``tbl[byte]`` spans 32 vregs along the gathered axis, and Mosaic gathers
within one.  ``tests/test_tpu_compile.py`` records the refusal;
``core.matcher`` refuses the ``shift_or`` lane on a compiled backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.device import on_cpu

BLOCK_N = 256


def _kernel(data_ref, tbl_ref, init_ref, final_ref, out_ref):
    blk_n, L = data_ref.shape
    Wb = tbl_ref.shape[1]
    tbl = tbl_ref[...]
    I = init_ref[...][0]                                        # (Wb,)
    F = final_ref[...][0]

    def body(i, carry):
        S, M = carry
        byte = data_ref[:, i]
        t = jnp.take(tbl, byte, axis=0)                         # (blk_n, Wb)
        S = ((S << jnp.uint32(1)) | I[None]) & t
        M = M | (S & F[None])
        return S, M

    S0 = jnp.zeros((blk_n, Wb), jnp.uint32)
    _, M = jax.lax.fori_loop(0, L, body, (S0, S0))
    out_ref[...] = M


@functools.partial(jax.jit, static_argnames=("block_n",))
def shift_or_kernel(data, tbl, init_mask, final_mask, *,
                    block_n: int = BLOCK_N):
    """data: (N, L) int32 byte values; tbl: (256, Wb) uint32;
    init_mask/final_mask: (1, Wb) uint32 -> (N, Wb) uint32 match words."""
    N, L = data.shape
    Wb = tbl.shape[1]
    assert N % block_n == 0
    return pl.pallas_call(
        _kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, L), lambda i: (i, 0)),
            pl.BlockSpec((256, Wb), lambda i: (0, 0)),
            pl.BlockSpec((1, Wb), lambda i: (0, 0)),
            pl.BlockSpec((1, Wb), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, Wb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Wb), jnp.uint32),
        interpret=on_cpu(),
    )(data, tbl, init_mask, final_mask)
