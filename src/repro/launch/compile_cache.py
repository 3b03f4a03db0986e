"""JAX's persistent compilation cache, placed for the entry points.

Each entry point (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``, ``benchmarks/run.py``) calls :func:`use_compile_cache`
once before its first compile; nothing calls it at import, so library
users and the tests keep JAX's default (no persistent cache).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
directory is left to it. Otherwise the cache lives at a fixed
``<checkout>/.jax_cache``: the path is part of what makes a later process
find the entry, so it holds no temp name, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
