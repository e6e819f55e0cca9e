"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``launch.serve``, ``launch.train``,
``benchmarks/run.py``) calls :func:`setup_compile_cache` before its first
compile.  A cache hit needs the same directory from run to run (the path
is part of the key), so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR``
when it is set — JAX reads that variable itself and nothing else is set
here — and otherwise ``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The cache directory the entry points use (no side effects)."""
    return os.environ.get(ENV) or str(REPO_ROOT / ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir`; returns
    it.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
