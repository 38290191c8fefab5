"""JAX's persistent compilation cache, placed for this repository.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`enable_compile_cache` before their first compilation; importing
``repro`` never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and no other directory is set here.  Otherwise the cache
lives in ``<repo>/.jax_cache``, a path resolved from this file's location:
the directory is part of the cache's key, so it must not move between
runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every compiled program is cached, however quickly it compiled: the
    query engine compiles many small circuit runners, and each of them
    would otherwise be compiled again by every cold process.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
