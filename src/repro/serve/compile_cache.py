"""Where the entry points keep JAX's persistent compilation cache.

The cache directory is part of every cache key, so it must not move between
runs: a temporary, pid- or time-derived path would never hit.  The entry
points (``chip_smoke.py``, ``serve.reach_server.main``,
``examples/dynamic_reachability.py``) call :func:`enable_compile_cache` once
at start-up; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the fixed in-checkout default (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing here overrides it.  Otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
