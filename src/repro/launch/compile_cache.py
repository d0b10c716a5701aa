"""Where JAX keeps its persistent compilation cache.

Called from the ``main()`` of each entry point, never at import: a library
import must not change process-wide JAX configuration.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: fixed in-checkout location (git-ignored): the cache key includes nothing
#: of the path, but a path that moves between runs never hits
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place the cache and return its directory.  ``JAX_COMPILATION_CACHE_DIR``
    set in the environment wins and JAX reads it itself; otherwise the cache
    goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
