"""JAX's persistent compilation cache for the repo's entry points.

A process that compiles the serving kernels (``chip_smoke.py``,
``python -m benchmarks.run``) calls :func:`enable_compile_cache` before its
first compile, so a later process on the same machine finds the compiled
programs again.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already
reads it and this sets no other directory; otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (ignored by git) -- the path is part
of the cache key, so it must not move.  Nothing here runs on import, and
tests never call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile (Pallas kernels
    compile in well under the default 1 s threshold, so the threshold goes
    to 0).  Returns the cache directory in use."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
