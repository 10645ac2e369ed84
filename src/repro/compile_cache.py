"""Where JAX keeps its persistent compilation cache for this repository.

Entry points call `enable_compile_cache()` from their ``main()``, never
on import. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read
it and its cache lives there. Otherwise the cache goes to `CACHE_DIR`,
one fixed directory inside the checkout (listed in ``.gitignore``): the
path is part of each entry's key, so a directory that moved between runs
would never hit.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
