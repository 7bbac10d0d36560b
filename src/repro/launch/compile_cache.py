"""JAX's persistent compilation cache at a fixed place.

The cache key includes the cache directory, so a directory that moves
between runs never hits. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it at start-up and this module sets nothing; otherwise the cache
lives in ``<repo>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile;
    returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
