"""Where the entry scripts keep JAX's persistent compilation cache.

A cold run on a TPU compiles every sweep at its real size, which can take
a large share of the run.  The cache makes later runs of the same checkout
skip those compiles.  Its directory is part of what a run is keyed on, so
it must not move from run to run.
"""
from __future__ import annotations

import os

import jax

__all__ = ["configure"]


def configure(root: str) -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the
    variable itself, so nothing is set here); otherwise keep the cache at
    ``<root>/.jax_cache``.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
