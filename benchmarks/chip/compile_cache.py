"""JAX's persistent compilation cache at a fixed path inside the checkout.

The directory is part of the cache's key, so it never moves: the one
``JAX_COMPILATION_CACHE_DIR`` names when that is set, else
``benchmarks/chip/.jax_cache`` (listed in ``.gitignore``)."""
from __future__ import annotations

import os
from pathlib import Path

import jax

DIR = Path(__file__).resolve().parent / ".jax_cache"


def enable() -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
