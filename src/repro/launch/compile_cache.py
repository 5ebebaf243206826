"""JAX's persistent compilation cache, placed from outside the program.

Call ``use_compile_cache()`` from an entry point's ``main`` (never at
import time).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
already read it and that directory stands.  Otherwise the cache goes
to ``<repo>/.jax_cache``: a fixed path, because a directory that moves
between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
