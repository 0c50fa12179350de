"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (chip_smoke.py, benchmarks/run.py) call `enable_compile_cache`
once, before their first compile; importing the library sets nothing.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
    sets nothing else. Otherwise the cache lives at the fixed path
    `<checkout>/.jax_cache` (git-ignored). The path is part of the
    cache's key, so it is never built from a temporary name, a pid or
    the time: a directory that moves between runs never hits.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
