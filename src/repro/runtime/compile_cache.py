"""JAX's persistent compilation cache, kept where every process finds it."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Fixed and inside the checkout (gitignored): a cache whose directory moves
#: between runs is never hit again.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and keeps
    the cache there, so no other directory is set.  Otherwise the cache goes
    to ``<repo>/.jax_cache``.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
