"""JAX's persistent compilation cache at a fixed path.

A cache directory is part of the cache's key, so a path that moves between
runs (a temp name, a pid, a timestamp) never hits. Entry points call
``enable_compile_cache()`` from their ``main()``; importing this module
changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — src/repro/launch/compile_cache.py is three levels down
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory: the one
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads it itself),
    else ``DEFAULT_DIR`` inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
