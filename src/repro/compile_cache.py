"""Where JAX keeps its persistent compilation cache.

Every entry point calls :func:`enable_compile_cache` before its first
compile.  A full-width model compiles one prefill program per (wave size,
prompt length) and one decode program per batch size; with the cache on,
a second process reuses them from disk.  The cache key includes the
directory, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it, otherwise ``.jax_cache`` at the root of this
checkout (listed in ``.gitignore``).

JAX writes only programs that took at least a second to compile by
default.  A decode step compiles in less, so under that default a second
process recompiled nearly every serving program; every program is
written here.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
