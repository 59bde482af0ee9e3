"""JAX's persistent compilation cache for the repo's entry points
(`chip_smoke.py`, `python -m repro.launch.serve`, `benchmarks/run.py`).

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives in one fixed directory of the
checkout, `.jax_cache`: the path is part of the cache key, so it never
moves between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on for this process; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
