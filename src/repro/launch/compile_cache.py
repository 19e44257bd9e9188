"""JAX's persistent compilation cache for the entry points.

The cache directory is part of every entry's key, so it must not move
between runs: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads
it itself), otherwise a fixed directory in the checkout.  Entry points
call ``enable_compile_cache`` from ``main``; importing this module
changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored); this file is src/repro/launch/
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(CACHE_ENV, "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
