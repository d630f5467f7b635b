"""Where JAX keeps its persistent compilation cache for this repo's
programs.

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/build_index.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up; importing the library
never does.  The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that
is set, else the fixed ``<checkout>/.jax_cache`` (git-ignored).  The
path is part of the cache key, so it must not move between runs: never
a temp dir, a PID or a timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    import jax
    cache_dir = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
