"""Where entry points keep JAX's persistent compilation cache.

The one helper every script that drives a chip calls before its first
compile.  JAX itself reads ``JAX_COMPILATION_CACHE_DIR``; where it is set,
nothing here changes it.  Otherwise the cache goes to ``<repo>/.jax_cache``:
a fixed path, because the path is part of what a later run must find
again, and never a temporary or per-process one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository root (this file is ``<repo>/src/repro/compile_cache.py``)
REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
