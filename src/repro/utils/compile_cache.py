"""JAX's persistent compilation cache for the repo's entry points.

Entry points that compile a model step (``launch/serve.py``,
``chip_smoke.py``, ``benchmarks/serving.py``, ``benchmarks/openloop.py``)
call :func:`enable_compile_cache` before their first compilation, so a
second run of the same shapes loads its executables instead of
compiling them again.  Library code and the tests never turn it on.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at ``<repo>/.jax_cache``, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX then uses that directory on
    its own and nothing is set here.  Returns the directory in use."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
