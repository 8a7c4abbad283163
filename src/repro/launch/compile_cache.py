"""JAX's persistent compilation cache, placed from outside.

Entry points call :func:`use_compile_cache` once at start-up, before their
first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX
reads it itself, so no other directory is set here); otherwise the cache
lives at one fixed, git-ignored path in the checkout, ``<repo>/.jax_cache``.
The path is part of the cache key, so it never derives from a temp
directory, a pid or a clock.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
