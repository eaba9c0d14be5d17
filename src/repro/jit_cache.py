"""Persistent compilation cache for the repo's entry points.

jax reads ``JAX_COMPILATION_CACHE_DIR`` itself: when it is set, the cache
lives there and nothing here overrides it.  Otherwise the cache is a
fixed ``.jax_cache/`` at the checkout root — fixed, because the path is
part of the cache key, so a directory that moves never hits.

Scripts call :func:`enable_compile_cache` at their entry point, before
the first compile.  Importing the library never touches the setting.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

CHECKOUT_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory; returns
    the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
