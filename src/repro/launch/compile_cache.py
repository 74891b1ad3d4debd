"""Where JAX keeps its persistent compilation cache for this repo's runs.

``JAX_COMPILATION_CACHE_DIR``, when set, decides: JAX reads it on its
own, and nothing here overrides it.  Otherwise the cache lives at the
fixed path ``<repo>/.jax_cache`` (listed in ``.gitignore``), so
successive runs from one checkout find each other's compiled kernels —
the path is part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
