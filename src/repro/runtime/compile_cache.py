"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs.  A deployment places it with ``JAX_COMPILATION_CACHE_DIR``;
without that variable it lives at a fixed path inside the checkout (listed
in ``.gitignore``), never under ``HOME`` or the temp directory.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The in-checkout default: ``<repo>/.jax_cache``.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    :data:`DEFAULT_DIR`.  Only the cache directory is set; every other
    cache option keeps the value JAX or the environment gave it."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
