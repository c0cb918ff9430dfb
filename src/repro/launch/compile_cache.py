"""JAX's persistent compilation cache for entry points that compile for a
chip.

A cold process compiles every kernel and step program again; the cache
lets the next process at the same path load them instead. The path is
part of what the cache is keyed on, so it must never move between runs:
no temporary name, pid or time in it.
"""
from __future__ import annotations

import os

CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set. Otherwise the cache goes to ``<root>/.jax_cache``
    (``root`` is the checkout; the directory is git-ignored). Call before
    the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
