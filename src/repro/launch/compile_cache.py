"""JAX persistent compilation cache placement.

Entry points (``chip_smoke.py``, the examples, ``benchmarks/run.py``) call
``use_compile_cache()`` from their ``main()``; importing this module sets
nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing else is configured here.  Otherwise the cache goes to a fixed
directory inside the checkout (``<repo>/.jax_cache``, git-ignored): the
path is part of what a later run must find again, so it never carries a
temporary name, a pid or a timestamp.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
