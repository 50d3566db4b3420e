"""Where JAX keeps its persistent compilation cache.

Called at the start of each entry point's main (never at import): a chip
run that finds last run's compiled kernels skips minutes of compilation,
and the cache's path is part of its key, so it must not move between runs.
"""
from __future__ import annotations

import os

import jax

#: Checkout root: src/repro/runtime/compile_cache.py -> three levels up.
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))
)))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable itself
    and nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
