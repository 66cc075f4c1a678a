"""JAX's persistent compilation cache, at one fixed place per checkout.

The cache key includes the directory, so a cache that moves between runs
never hits.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here sets a directory of its own.  Otherwise the cache goes to
``<checkout>/.jax_cache``, found from this file's location rather than the
working directory (``.gitignore`` lists it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
