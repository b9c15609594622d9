"""Persistent JAX compilation cache, in one fixed place.

A cache hit needs the same directory on every run, so the path never
holds a temp name, a pid or a timestamp. ``JAX_COMPILATION_CACHE_DIR``,
when set, is the only location: JAX reads it itself and this module
sets nothing. Otherwise the cache lives in ``.jax_cache/`` at the root
of the checkout (gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile;
    returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
