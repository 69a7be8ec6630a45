"""Persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/mine.py``, ``launch/serve.py``)
call ``enable_compile_cache()`` once at start-up; nothing calls it at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that
directory itself and this sets no other.  Otherwise the cache lives at
the fixed ``<checkout>/.jax_cache`` (gitignored): the path is part of the
cache key, so a per-process or temporary directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    What is cached, and how much, follows JAX's own settings: by
    default every program that took at least a second to compile, with
    ``JAX_COMPILATION_CACHE_MAX_SIZE`` capping the directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
