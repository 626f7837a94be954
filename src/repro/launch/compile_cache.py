"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile; library code and tests never do. The directory is part of the
cache's key, so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` where that is
set (JAX reads it itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
