"""Placement of JAX's persistent compilation cache.

Every entry point calls `init_compile_cache()` before its first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing; otherwise the cache lives in ``.jax_cache/`` at the checkout
root.  The path is part of each entry's key, so a fixed path is what lets a
second run from the same checkout find the first run's executables.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
