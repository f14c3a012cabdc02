"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so the directory must not move
between runs: it is either what the deployment sets in
``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself) or the
fixed ``.jax_cache/`` at the repository root, never a temp, pid or time
based name. Entry points call `enable_compile_cache()`; library code and
tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. With
    ``JAX_COMPILATION_CACHE_DIR`` set nothing else is configured."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
