"""JAX's persistent compile cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and keeps
its cache there; nothing here sets another directory. Otherwise the cache
goes to ``.jax_cache/`` at the root of the checkout (gitignored). The path
is fixed, never built from a temporary name, a pid or the time, because a
cache whose directory moves is never found again.

Entry points call `enable_compile_cache()` once, before their first
compile; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the fallback directory: ``<checkout>/.jax_cache`` (this file lives at
#: ``<checkout>/src/repro/common/compile_cache.py``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    outside = os.environ.get(ENV_VAR)
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
