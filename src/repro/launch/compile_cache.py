"""One place that decides where JAX keeps its persistent compilation cache.

A cold process on the chip compiles every kernel and scan it runs; the
persistent cache lets later processes skip that.  The cache key includes
the directory, so the path must not move between runs: no temporary name,
process id or time stamp in it.  Entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``scripts/loadgen.py``,
``examples/serve_frontend.py``) call :func:`enable_compile_cache` before
their first compile; the library itself never does (``import repro`` stays
free of JAX).
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
