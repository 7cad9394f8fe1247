"""JAX's persistent compilation cache for the repo's entry points.

Call :func:`enable_compile_cache` before the first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache goes to ``<checkout>/.jax_cache`` (listed
in ``.gitignore``): a fixed path, because the directory is part of the
cache key — one that moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/cache.py -> the checkout root
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
