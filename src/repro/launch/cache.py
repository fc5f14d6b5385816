"""Persistent XLA compilation cache for the entry points.

A run on a fresh machine starts with no compiled code; the cache lets the
processes of one run, and later runs on the same disk, reuse compiles. JAX
keys a cache by its directory, so the directory is fixed:
``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the variable
itself), else ``<repo>/.jax_cache``. Entry points call
:func:`enable_compile_cache` first; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
