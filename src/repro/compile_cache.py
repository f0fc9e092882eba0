"""JAX persistent compilation cache placement, shared by every entry point.

A cold process recompiles the fused step once per capacity bucket; the
persistent cache turns the second run of the same shapes into a read.  The
cache key includes the directory, so the directory must not move between
runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
nothing here overrides it; otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(checkout: os.PathLike | str) -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``checkout`` is the repository root of the calling entry point.  Every
    compiled program is cached, however quick its compile: the smoke's
    per-bucket steps are each cheap, and together they are the cold start.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = Path(checkout).resolve() / CACHE_DIRNAME
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
