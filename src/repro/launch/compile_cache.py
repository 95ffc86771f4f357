"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``launch/serve.py``, ``chip_smoke.py``) call
``enable_compile_cache()`` once at start-up, never at import.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache lives at ``<checkout>/.jax_cache``.  The path
is fixed because it is part of every entry's key: a temporary or
per-process directory would never hit."""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
