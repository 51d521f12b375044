"""Where JAX keeps compiled executables between processes.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call :func:`use_jax_compile_cache` first thing;
importing this module changes nothing.  The path is part of what JAX
keys a cache entry on, so the default is one fixed directory in the
checkout, never a temporary, per-process or timestamped name.  The
repo's own graph cache (``REPRO_CACHE_DIR``, ``backend/diskcache.py``) is
separate and unaffected.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/runtime/...),
# listed in .gitignore
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_jax_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
    itself, so no other directory is set here), else :data:`DEFAULT_DIR`."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
