"""Placement of JAX's persistent compilation cache.

Entry points that run the device path call :func:`enable_compile_cache`
once, before their first compile; importing the library never sets a
cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and the function leaves it alone.  Otherwise the cache goes to a fixed
directory inside the checkout, ``<repo>/.jax_cache``: the directory is
part of the cache key, so it never depends on a temp name, a pid or a
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
