"""Where JAX keeps its persistent compilation cache.

`JAX_COMPILATION_CACHE_DIR`, when set, wins: JAX reads it itself and this
module sets nothing. Otherwise the cache lives at one fixed directory inside
the checkout, `<repo>/.jax_cache` (git-ignored). The directory is part of
the cache key, so a fixed path is what lets a later process hit it.

Call `setup_compile_cache()` before the first compile: the launchers and
`chip_smoke.py` do.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def setup_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
