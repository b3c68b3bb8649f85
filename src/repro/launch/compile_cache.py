"""JAX's persistent compilation cache for scripts that run on the chip.

A run on a fresh machine compiles every kernel and jitted step again; the
persistent cache lets the processes of one checkout share that work.  The
cache directory is part of each entry's key, so it must not move between
runs.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = ".jax_cache"  # under the checkout root; listed in .gitignore


def enable(root: str) -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``<root>/.jax_cache``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = os.path.join(os.path.abspath(root), CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
