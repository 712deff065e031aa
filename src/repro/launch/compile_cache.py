"""Where JAX keeps its persistent compilation cache.

A compiled program is cached under a key that includes the cache path, so
the path has to stay put between runs: a directory named after a pid, a
time or a temporary name never hits. Entry points call
``place_compile_cache()`` once, before their first compile; tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache goes to ``.jax_cache/`` at the
    root of the checkout (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
