"""Where JAX keeps its persistent compilation cache for this repo's scripts.

Entry-point scripts (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up; library code never configures
JAX when it is imported.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins and
JAX reads it itself.  Otherwise the cache goes to one fixed directory inside
the checkout, so a later run of the same checkout finds it again.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir(environ=os.environ) -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` or the default."""
    return environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
