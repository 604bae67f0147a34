"""JAX's persistent compilation cache, placed from outside the program.

A compile is written once and a later process with the same program finds
it again. ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and
JAX reads it itself, so nothing is configured here. Otherwise the cache
lives at one fixed path inside the checkout (``.jax_cache/``, gitignored):
the directory is part of each entry's key, so a path that moved between
runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
