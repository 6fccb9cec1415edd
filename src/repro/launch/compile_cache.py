"""JAX's persistent compilation cache, at a directory that stays put.

The cache key includes the directory, so a path that moves between runs
never hits. ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (a machine
that keeps a cache across runs sets it); otherwise the cache lives at one
fixed, gitignored path in the checkout. Entry points call
:func:`enable_compile_cache` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    path = os.environ.get(ENV_VAR) or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
