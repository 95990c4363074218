"""JAX's persistent compile cache for the repo's entry points.

Called from ``main()`` of ``chip_smoke.py``, the examples and the
benchmarks — never while a module is imported, so importing ``repro``
changes no JAX setting.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and is left alone (JAX
    reads it itself). Otherwise the cache is ``<repo>/.jax_cache``: a
    fixed path, so a later run of the same checkout finds its entries.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
