"""JAX's persistent compilation cache, placed once per process.

Call :func:`enable_compile_cache` from a program's entry point before its
first compile; importing the library never touches the cache.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else is
configured.  Otherwise the cache lives at one fixed, git-ignored path inside
the checkout (``<repo>/.jax_cache``): the directory is part of the cache key,
so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
