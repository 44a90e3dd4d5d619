"""JAX's persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing is set here.  Otherwise the cache lives at ``.jax_cache`` in the
checkout root — a fixed path, since a cache whose directory moves never
hits.  The directory is git-ignored.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
