"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, `repro.launch.train`)
call `enable_compile_cache()` once at startup; importing the package never
touches the cache.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: <repo root>/.jax_cache — fixed, because the directory is part of what a
#: later run must find; never built from a temp name, a pid or the time.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to `REPO_CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
