"""JAX's persistent compilation cache for the entry points.

Called by each entry point's ``main`` (never at import), so a second run
of the same program skips the compiles the first one paid for.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's root (this file is <root>/src/repro/launch/compile_cache.py)
ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself, so nothing is set here). Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache``: the path is part of the cache key, so
    it must not move from run to run.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
