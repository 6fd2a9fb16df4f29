"""JAX's persistent compilation cache, placed from outside or at one path.

``chip_smoke.py`` calls :func:`enable_compile_cache` before it compiles
anything.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
nothing is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, so a later process in the same checkout finds what an
earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
