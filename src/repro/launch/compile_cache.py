"""Placement of JAX's persistent compilation cache.

Only entry points call :func:`use_compile_cache` (the launch drivers'
``main()``, ``chip_smoke.py`` and the benchmark's ``run.py``), before their
first compile and never at import: library code and tests leave JAX's
cache configuration and its monitoring listeners alone.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.core.profiler import watch_compiles

# a fixed path: the directory is part of each entry's key, so a cache that
# moves with the caller's working directory would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken it
    and nothing else is set.  Otherwise the cache lives in ``.jax_cache/``
    at the repository root.  Either way every compile from here on is
    counted by program in ``profiler.SPANS`` (``watch_compiles``)."""
    watch_compiles()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    REPO_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
