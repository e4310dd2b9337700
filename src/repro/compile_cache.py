"""JAX's persistent compilation cache for the entry points.

The segment bodies of the main path take tens of seconds each to
compile for a TPU; with the cache on, the next process in the same
checkout loads them instead.  The directory is part of the cache key,
so it is a fixed path, never a temporary name.  The entry points
(``python -m repro.api``, ``chip_smoke.py``, the benchmarks' ``main()``)
call :func:`enable_compile_cache`; importing ``repro`` does not, and
neither do the tests.
"""

from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache (this file is <checkout>/src/repro/compile_cache.py)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting for
    the directory and is left alone; otherwise the cache goes to
    :data:`CACHE_DIR`.  Call it before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
