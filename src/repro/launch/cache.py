"""JAX's persistent compilation cache for the entry points.

A chip machine starts with no compiled code, and a BERT-base step takes
minutes to compile; the cache lets later processes on the same disk skip
that. ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
itself) and no other directory is set here. Otherwise the cache is the
fixed ``.jax_cache/`` at the repository root (git-ignored): the directory
is part of what a cached entry is found by, so it never depends on a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
