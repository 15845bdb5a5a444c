"""Persistent XLA compile cache, placed the same way by every entry point.

A full-width step program takes tens of seconds to compile on the TPU;
the cache lets the next process load it instead.  Entry points (the
``launch`` CLIs, the benchmark mains and ``chip_smoke.py``) call
``enable_compile_cache()`` once at start-up; library code and tests never
do, so importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Returns the cache directory in use.  ``JAX_COMPILATION_CACHE_DIR``,
    when set, is the place: JAX reads it itself, and nothing is set
    here.  Otherwise the cache lives at ``<checkout>/.jax_cache`` — a
    fixed path, because a directory that moves between runs never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
