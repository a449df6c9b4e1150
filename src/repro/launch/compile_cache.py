"""Persistent compilation cache placement for the launchers.

A full-width serving step takes tens of seconds to compile, and a fresh
process compiles every step shape again unless JAX's persistent cache
holds it. The cache key includes the directory, so the directory must not
move between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself, and nothing here overrides it) or
``<checkout>/.jax_cache``, found from this package's own path rather than
the working directory. Called at the start of a launcher, never at
import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
