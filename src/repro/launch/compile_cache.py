"""Persistent XLA compilation cache shared by every entry point.

A full-width step program takes tens of seconds to compile; the cache
lets a later process (the next smoke run, a benchmark after a warm-up)
load it instead. JAX keys cache entries on the directory path among
other things, so the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that variable itself), otherwise
``<repo>/.jax_cache`` next to ``src/`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

import jax

#: the repository root: src/repro/launch/compile_cache.py → three levels up
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__
    ))))
)
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation. Sets no directory when
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX already uses it.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
