"""Persistent XLA compilation cache for the entry points.

Called from the entry points' ``__main__`` blocks (and ``chip_smoke.py``),
never at import, so library users and tests keep jax's own defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
is overridden: the deployment places the cache.  Otherwise the cache lives
at a fixed directory inside the checkout — the path is part of what makes
a later run find its entries, so it never depends on a temp name, a pid or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
