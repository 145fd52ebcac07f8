"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start, so
a second run of the same program loads its executables instead of
compiling them again. The directory is part of every entry's key, so it
must not move between runs: it is either the one the environment names
or a fixed directory in the checkout, never one built from a temp name,
a pid or the time. Tests do not call this.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
