"""Process-level JAX settings shared by the entry points.

Each entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls
:func:`setup_compile_cache` from its ``main``; importing this module
changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout (gitignored): the cache directory is part of
# each entry's key, so a path that moved between runs would never hit
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
