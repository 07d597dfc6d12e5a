"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/``) calls :func:`enable` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and nothing
here overrides it.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (ignored by git): never a temp, pid- or
time-derived path, so a later run from the same checkout finds what an
earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
