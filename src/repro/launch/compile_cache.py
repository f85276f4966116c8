"""Where the launch path keeps JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout and ignored by git: the cache key includes
# the path, so a directory named after a temp dir, pid or time never hits
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to the checkout's
    ``.jax_cache/``.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
