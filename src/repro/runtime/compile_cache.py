"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/serve.py``, ``benchmarks/run.py``, ``chip_smoke.py``)
call ``enable_compile_cache()`` once before they compile anything; nothing
calls it at import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and this sets nothing.  Otherwise the cache goes to one fixed
directory inside the checkout (``<repo>/.jax_cache``, git-ignored): the
path is part of the cache key, so it is never derived from a temp name, a
pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get(ENV, "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
