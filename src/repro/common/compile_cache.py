"""JAX's persistent compilation cache, switched on before the first compile.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` first thing, so a second run of the
same program on the same machine reads its compiled executables back
instead of compiling again.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
that directory is the cache and no other is set.  Otherwise the cache is
``<checkout>/.jax_cache`` — a fixed path (the cache key includes nothing
that moves), listed in ``.gitignore``.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV) or os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
