"""Ahead-of-time compiles of the main-path kernels for a TPU v5e chip.

The chip is described (``v5e:2x2``), not attached: these tests lower and
compile at real widths with the TPU compiler installed here, so a kernel
Mosaic refuses fails here rather than on the chip.  Nothing runs.  The
topology is described inside a fixture (never at import), and every chip
compile lives in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.matcher import TPU_REFUSED

N_QUERY = 1 << 20        # one bitmap-column stack over ~1M records
F, N, L, S, C, W = 2, 4096, 512, 4096, 64, 32   # 1000-rule ingest batch


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip, monkeypatch):
    """-> compile(fn, (shape, dtype), ...) for the described chip.  Kernels
    see a TPU default backend (so they take their Mosaic path, not the
    interpreter), and the persistent compile cache is off: a TPU entry
    written here could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()          # no trace from an interpreting CPU test

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_ingest_dfa_ref_batch(compile_tpu):
    """The default ingest lane: plain XLA, no Pallas kernel."""
    from repro.kernels.dfa_scan.ops import _dispatch_fused
    c = compile_tpu(
        lambda d, lut, de, em: _dispatch_fused(
            d, lut, de, em, eng_idx=(0, 1), backend="ref", block_n=256),
        ((F, N, L), jnp.uint8), ((F, 256), jnp.int32),
        ((F, S, C), jnp.int32), ((F, S, W), jnp.uint32))
    assert _kernel_calls(c) == 0
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


def test_query_word_dispatch_pallas(compile_tpu):
    """The query executor's only Pallas kernel, with device counts."""
    from repro.kernels.bitmap_filter.ops import _word_query_dispatch
    c = compile_tpu(
        lambda cols, bits, seg: _word_query_dispatch(
            cols, bits, seg, num_segments=32, backend="pallas",
            block_n=1024, with_counts=True),
        ((N_QUERY, 2), jnp.uint32), ((2,), jnp.uint32),
        ((N_QUERY,), jnp.int32))
    assert _kernel_calls(c) >= 1


def test_bitmap_query_kernel(compile_tpu):
    from repro.kernels.bitmap_filter.bitmap_filter import bitmap_query_kernel
    c = compile_tpu(bitmap_query_kernel, ((N_QUERY, W), jnp.uint32),
                    ((2, W), jnp.uint32))
    assert _kernel_calls(c) >= 1


def test_bitmap_filter_kernel(compile_tpu):
    from repro.kernels.bitmap_filter.bitmap_filter import bitmap_filter_kernel
    c = compile_tpu(bitmap_filter_kernel, ((N_QUERY, W), jnp.uint32),
                    ((1, W), jnp.uint32))
    assert _kernel_calls(c) >= 1


def _dfa_scan(data, luts, deltas, emits):
    from repro.kernels.dfa_scan.dfa_scan import dfa_scan_fused_kernel
    return dfa_scan_fused_kernel(data, luts, deltas, emits,
                                 jnp.arange(F, dtype=jnp.int32))


def _shift_or(data, tbl, init_mask, final_mask):
    from repro.kernels.shift_or.shift_or import shift_or_kernel
    return shift_or_kernel(data, tbl, init_mask, final_mask)


@pytest.mark.parametrize("lane, fn, shapes, words", [
    ("dfa", _dfa_scan,
     (((F, N, L), jnp.uint8), ((F, 256), jnp.int32),
      ((F, S, C), jnp.int32), ((F, S, W), jnp.uint32)),
     "Only 2D gather is supported"),
    ("shift_or", _shift_or,
     (((N, L), jnp.int32), ((256, 128), jnp.uint32),
      ((1, 128), jnp.uint32), ((1, 128), jnp.uint32)),
     "Shape mismatch in input, indices and output"),
])
def test_refused_kernels_stay_refused(compile_tpu, lane, fn, shapes, words):
    """The record in ``matcher.TPU_REFUSED`` is the compiler's current
    answer: once a rewrite makes one of these compile, this fails and the
    lane's refusal goes."""
    assert words in TPU_REFUSED[lane]
    with pytest.raises(Exception, match=words):
        compile_tpu(fn, *shapes)
