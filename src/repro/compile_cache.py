"""JAX's persistent compilation cache, set up the same way by every entry
point (``chip_smoke.py``, ``benchmarks/run.py``, the examples).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the directory from
it and nothing is set here. Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (git ignores it): the directory is part of what a
cached entry is found by, so it is never built from a temporary name, a
pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. The fused
    kernels compile in about a second, under JAX's default minimum compile
    time for caching, so the minimum is lowered to zero."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
