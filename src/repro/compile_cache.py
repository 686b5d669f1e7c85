"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``launch/simulate.py``, ``launch/serve.py``,
``benchmarks/bench_delivery.py``, ``chip_smoke.py``) call
:func:`enable_compile_cache` once, before their first compile; importing
the library never touches the cache. The cache key includes the directory,
so the directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads it itself, and no other directory is set here),
else ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["compile_cache_dir", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/compile_cache.py -> the checkout root.
_CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    (unless the environment variable already does) and return the path."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
