"""Persistent XLA compilation cache for the entry points.

The cache key includes the directory, so the directory is fixed: either
the one ``JAX_COMPILATION_CACHE_DIR`` names, or ``.jax_cache/`` at the
root of the checkout.  Only entry points (``train.main``, ``serve.main``,
``chip_smoke.py``) turn it on; importing the package never does, because
tests import it.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
