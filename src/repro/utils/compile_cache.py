"""JAX's persistent compilation cache for the repository's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the root of
the checkout (git-ignored): a fixed path, because the path is part of
the cache key and a per-process or temporary directory never hits.
Called once by ``chip_smoke.py``, ``benchmarks/run.py`` and the examples;
the library and the tests never set a cache.
"""
from __future__ import annotations

import os
import pathlib

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX at the compile cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
