"""JAX's persistent compilation cache at one fixed place.

A compiled program is keyed by, among other things, the cache's own
path, so a directory named after a temp dir, a pid or the time never
hits again.  Entry points that compile for a device call
:func:`enable_compile_cache` before their first jit.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins, and JAX reads it by itself, so
    no path is set in code then.  Otherwise the cache lives in
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
