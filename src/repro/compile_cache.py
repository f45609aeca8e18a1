"""JAX's persistent compilation cache, shared by every path that
compiles: profiling trials, the in-process backends and the per-job
worker processes (which import this module without ``repro.core``).

The directory is ``JAX_COMPILATION_CACHE_DIR`` where that is set, and
otherwise :data:`CACHE_DIR`, one fixed directory inside the checkout: a
directory that moves between runs never hits.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir` and
    cache every entry, however small or quick to compile (Saturn's trial
    grids are many small steps).  Idempotent; returns the directory."""
    d = cache_dir()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
        # a cache JAX already opened keeps its old directory until reset
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    return d
