"""JAX's persistent compilation cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set. Otherwise the cache
lives at a fixed path inside the checkout (``.jax_cache/`` at the repo
root, git-ignored): the path is part of the cache key, so it must not
carry a temp name, a pid or a time, or a second run never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "compile_cache_dir",
           "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory: the environment's, else the in-repo default."""
    return os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir`; call it
    before the first compile. Returns the directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
