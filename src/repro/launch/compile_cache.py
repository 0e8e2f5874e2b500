"""JAX's persistent compilation cache, placed from outside the program.

Every entry point that compiles at full size (``launch.quantize``,
``launch.serve``, ``benchmarks.run``, ``chip_smoke.py``) calls
:func:`setup_compile_cache` before its first compile:

- with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory itself
  and this module sets nothing;
- otherwise the cache goes to ``<checkout>/.jax_cache``. The path is part
  of a cache entry's key, so it is fixed — never a temporary name, a pid or
  the time — and a second run from the same checkout finds the first
  one's compiles.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
