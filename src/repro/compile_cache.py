"""Where JAX keeps its persistent compilation cache.

One helper for every entry point (``chip_smoke.py``, ``python -m
repro.fed.run``, ``python -m benchmarks.run``). The cache key includes the
directory, so the directory must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself and this module
  sets nothing;
* otherwise ``<checkout>/.jax_cache``, resolved from this file's location
  (``src/repro/compile_cache.py`` -> the checkout root).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout this package runs from
CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> Optional[str]:
    """The directory :func:`enable_compile_cache` sets, or None when
    ``JAX_COMPILATION_CACHE_DIR`` already names one."""
    if os.environ.get(ENV):
        return None
    return str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    (call before the first compile). Returns the directory set, or None
    when the environment variable decides."""
    path = compile_cache_dir()
    if path is not None:
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
