"""Persistent XLA compilation cache for the entry points.

Launchers, the benchmark and ``chip_smoke.py`` call
:func:`enable_compile_cache` once at startup, so that a process that compiles
a shape another process already compiled reads it back instead.  Importing
this module (or the library) never turns the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: Fixed in-checkout cache directory.  The path is part of each entry's key,
#: so it must not move between runs (no temporary names, pids or times).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this function sets nothing; otherwise the cache goes to
    :data:`CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
