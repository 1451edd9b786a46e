"""Where JAX keeps compiled programs between processes.

Each stepper envelope and each model step takes seconds to compile, and a
fresh process pays for all of them again unless JAX's persistent
compilation cache is on. Entry points (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile.
"""
from __future__ import annotations

from pathlib import Path

#: the fixed fallback location: ``<repo>/.jax_cache`` (gitignored)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    it is left alone; otherwise the cache goes to ``REPO_CACHE_DIR``."""
    import jax
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
