"""Where JAX keeps its persistent compilation cache.

The cache key includes the cache's path, so a directory that moves never
hits. ``JAX_COMPILATION_CACHE_DIR``, when set, is the deployment's choice:
JAX reads it itself and nothing here touches it. Otherwise the cache goes
to one fixed directory inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory. Call once at an entry point, before the first compile."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
