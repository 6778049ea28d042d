"""Where JAX's persistent compilation cache lives.

Entry points call `enable_compile_cache()` before their first compile;
importing `repro` never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache's place when the environment names none: fixed, because the
#: directory is part of what a later run must find again
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Every program is kept, however quickly it compiled: JAX's default
    keeps only those that took a second or more, and a program that
    compiles in half a second (a decode step on the chip) would be
    compiled again by every process.

    `JAX_COMPILATION_CACHE_DIR`, where set, places the cache: JAX reads
    that variable itself, so no directory is set here. Otherwise the
    cache goes to `<checkout>/.jax_cache`."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
