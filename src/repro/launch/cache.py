"""Persistent XLA compilation cache for the entry points.

Serving and training programs at real widths take tens of seconds to
compile; the cache lets a later process (a restarted server, the next
benchmark run) load them instead. The cache key includes the directory,
so the default is one fixed path in the checkout, never a temporary one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored); this file is src/repro/launch/cache.py
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here; otherwise the cache goes to ``DEFAULT_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
