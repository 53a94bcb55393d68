"""Where the command-line entry points keep JAX's persistent compile cache.

The library and the tests set no cache. A CLI calls
:func:`enable_compile_cache` once, before its first compile: JAX itself
reads ``JAX_COMPILATION_CACHE_DIR`` when it is set, and otherwise the
cache goes to ``<repo>/.jax_cache`` — a fixed path, because the path is
part of the cache key.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
