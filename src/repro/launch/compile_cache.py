"""Persistent compilation cache for the launch entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set
nothing here overrides it. Where it is not, the cache lives at the fixed
path ``<repo root>/.jax_cache`` (git-ignored): the directory is part of
what a later run looks up, so a path built from a temporary name, a pid
or a time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache before the first compile;
    returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
