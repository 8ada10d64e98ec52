"""Where JAX keeps its persistent compilation cache for this repo's
entry points (``chip_smoke.py``, ``benchmarks/run.py``).

Called by entry points only, never at library import. The cache key
includes the directory, so the directory is fixed: a path built from a
temporary name, a pid or the time would never hit again.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left as it is: JAX reads
    it itself and no other directory is set here. Otherwise the cache
    goes to ``<repo>/.jax_cache`` (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
