"""Where JAX keeps its persistent compilation cache.

One rule for every entry point that compiles at deployment size
(``chip_smoke.py``, ``benchmarks/run.py``): if the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and nothing here
overrides it; otherwise the cache lives at a fixed directory inside the
checkout (``.jax_cache/``, git-ignored).  A run finds what earlier runs
compiled only at the same path, so the path never moves between runs:
it is never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
