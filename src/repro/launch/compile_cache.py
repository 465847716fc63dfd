"""Persistent XLA compilation cache shared by every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
nothing is changed here.  Otherwise the cache lives at a fixed path
inside the checkout (``<repo>/.jax_cache``, gitignored): the directory
is part of what a later process looks up, so it must not move between
runs.  Call ``enable_compile_cache()`` before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
