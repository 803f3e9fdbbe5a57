"""Where JAX keeps its persistent compilation cache.

A compiled program is keyed by, among other things, the cache's path, so
the path has to stay put from run to run: never a temp name, a pid or a
timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_ROOT", "use_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout's root
REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compile cache before the first compile and
    return its directory.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it on its own and nothing else is set here; otherwise the cache
    lives at ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
