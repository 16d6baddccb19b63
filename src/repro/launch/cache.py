"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`use_compile_cache` at the top of their
``main()`` (never at import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing is changed here.  Otherwise the cache
goes to ``<checkout>/.jax_cache``: a fixed path, because the cache key
includes it, so a directory that moves from run to run never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
