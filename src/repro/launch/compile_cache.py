"""JAX's persistent compilation cache, kept at one fixed place.

The cache directory is part of what JAX matches an entry against, so it
must not move between runs: a name built from a temporary directory, a pid
or the time would never hit. Entry points call :func:`enable_compile_cache`
once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
    and nothing is set here; otherwise cache under ``<checkout>/.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
