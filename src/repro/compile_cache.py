"""Where JAX keeps compiled programs between processes.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself.  Where it is not set,
an entry point that wants its compilations back on the next run names a
fixed directory: one named anew per run (a temporary name, a process id,
the time) never hits.  Entry points call :func:`enable_compile_cache`;
nothing calls it on import, so the tests write no cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]


def enable_compile_cache(default_dir) -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX already uses
    it, so nothing is changed), else ``default_dir``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(default_dir).resolve())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
