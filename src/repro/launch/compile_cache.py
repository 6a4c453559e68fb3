"""Where the entry points keep JAX's persistent compilation cache.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise, on a TPU backend, the cache goes to
``<checkout>/.jax_cache`` (gitignored). The path is fixed on purpose: it is
part of what a later run has to find again, so it never comes from a temp
name, a pid or the time. CPU runs (tests, socket workers) keep no default
cache: their compiles are cheap, and XLA:CPU logs an error for each entry
it loads back.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compile cache; returns its directory (None
    when there is none). Call it before the first compilation of the
    process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
