"""JAX's persistent compilation cache for the launchers.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself); any
other run caches in one fixed directory inside the checkout. The cache key
includes the directory, so the path never depends on a temporary name, a PID
or the time: a second run in the same checkout finds what the first compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
