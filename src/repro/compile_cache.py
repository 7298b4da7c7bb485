"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing. If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here. Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout. The path is fixed on purpose: it is part of each entry's key, so
a directory named after a process, a time or a temporary name never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
