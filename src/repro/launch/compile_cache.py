"""Persistent XLA compile cache for entry points.

Only an entry point's `main()` calls `enable_compile_cache`; importing
this module (or any library module) never sets a cache directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed path: the directory is part of every cache key, so it must not
# depend on a temporary name, a process id or the time
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across runs and return the directory:
    `$JAX_COMPILATION_CACHE_DIR` when set (JAX reads it itself, so no
    other directory is set), else `<repo>/.jax_cache`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
