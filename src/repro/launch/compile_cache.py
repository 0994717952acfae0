"""JAX's persistent compilation cache for the entry points.

Called by ``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks.run``
only — library imports and tests leave the cache configuration alone.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache lives at ``<repo root>/.jax_cache``: a
fixed path, because the path is part of the cache key, so a later run of
the same program on the same machine finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
