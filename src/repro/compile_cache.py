"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``repro.launch.ingest``,
``repro.launch.serve``, ``benchmarks.run``) call ``enable()`` once, before
their first compile.  No library module touches the cache on import.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed directory inside the checkout (git ignores it): the cache key
# includes the path, so a directory that moves never hits
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> None:
    """Leave JAX alone when ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads
    it itself); otherwise keep the cache in ``CACHE_DIR``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
