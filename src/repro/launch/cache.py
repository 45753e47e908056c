"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``,
``launch/train.py``) call :func:`use_compile_cache` before their first
compile; library modules never do.  The cache key includes the path, so
the directory is fixed: never a temporary name, a pid or a timestamp.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout root: src/repro/launch/cache.py -> three levels up
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``.jax_cache/`` at the
    root of the checkout."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
