"""JAX persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call ``enable_compile_cache()`` from their ``main``; nothing enables it at
import time.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps
its cache there and no other directory is set here.  Otherwise the cache
lives at a fixed path inside the checkout (``<repo>/.jax_cache``, listed in
``.gitignore``): the path is part of the cache key, so a directory that
moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the probe kernels and pool steps compile in well under a second;
    # the default 1 s floor would never cache them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
