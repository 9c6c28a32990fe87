"""JAX's persistent compilation cache for the entry points.

Full-width training and serving programs take tens of seconds each to
compile; the persistent cache lets a second process on the same machine
load them instead.  Entry points call `enable_compile_cache` once at start;
importing this module changes nothing.
"""
from __future__ import annotations

import os

import jax

# <repo>/.jax_cache: a fixed path, because JAX keys cache entries by
# directory — a per-run name would never hit
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here.  Otherwise the cache lives in `CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
