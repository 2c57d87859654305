"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only at the same path, so the cache
directory is placed from outside the program or fixed inside the
checkout — never a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` (git-ignored)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
