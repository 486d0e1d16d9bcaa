"""Where JAX keeps its persistent compilation cache.

The cache directory is part of every entry's key, so it must not move
between runs: a temporary name would never hit. Entry points call
:func:`enable_compile_cache` from their ``main()``; importing this module
changes nothing.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/runtime/...``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to the fixed
    :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
