"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache`` is called by ``repro.launch.train.main`` and
``chip_smoke.py`` — never on import.  When ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here overrides it.  Otherwise
the cache goes to ``<repo>/.jax_cache`` (gitignored): a fixed path,
because the path is part of every entry's key, so a directory that
moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
