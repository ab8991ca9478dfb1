"""Where JAX keeps its persistent compile cache for the entry-point scripts.

The cache directory is part of every entry's key, so it must not move
between runs: `JAX_COMPILATION_CACHE_DIR` wins when it is set (JAX reads
it itself), and otherwise the cache lives at the fixed `<checkout>/.jax_cache`
(git-ignored). Only entry points call this — never tests, never an import.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_persistent_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
