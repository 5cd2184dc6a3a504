"""JAX's persistent compilation cache for the entry points that run on a
chip (the launchers and ``chip_smoke.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module changes nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``:
a fixed path, because the path is part of what a later process must find.
Never called at import time or from tests.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
