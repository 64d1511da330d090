"""JAX's persistent compilation cache for the entry points that compile
(chip_smoke.py, cmd/scheduler.py, the benchmark harness).

The directory is part of every cache key, so it must be stable across
runs: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself and
this sets nothing), else one fixed git-ignored directory inside the
checkout. Never called at import and never from the tests."""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
