"""Where the repo's entry scripts keep JAX's persistent compilation cache.

``benchmarks/run.py`` and ``chip_smoke.py`` call :func:`setup_compile_cache`
before their first compile; the library itself sets no cache.  When
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it on its own and nothing
is set here.  Otherwise the cache goes to a fixed directory inside the
checkout: the path is part of the cache key, so a directory that moved
between runs would never hit.
"""
from __future__ import annotations

import os

CACHE_DIR = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             ".jax_cache"))


def setup_compile_cache() -> str:
    """Point this process's jax at :data:`CACHE_DIR`; returns it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
