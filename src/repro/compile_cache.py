"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
:func:`enable_compile_cache` once, before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this module
sets no other directory; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` — a path that never moves between runs of one
checkout, which is what lets a later run hit it.  Library code never calls
this: importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV_VAR", "default_cache_dir", "enable_compile_cache"]

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# cache every compile that takes longer than this — low enough that the
# one-to-two-second Pallas kernel compiles are kept too (JAX's default is 1 s)
MIN_COMPILE_SECS = 0.1


def default_cache_dir() -> pathlib.Path:
    """``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/``)."""
    return pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    cache_dir = os.environ.get(CACHE_ENV_VAR)
    if not cache_dir:
        cache_dir = str(default_cache_dir())
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return cache_dir
