"""One place for JAX's persistent compilation cache.

The cache key includes the cache path, so the path must not move between
runs.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it; otherwise the cache lives in ``.jax_cache/`` at
the root of the checkout (listed in ``.gitignore``).  Entry points call
`use_compile_cache()` before their first compile; importing `repro` never
touches the cache.
"""
from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on at its one place; returns the path."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
