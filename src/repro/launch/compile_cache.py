"""Where JAX's persistent compilation cache lives — decided here only.

Every entry point that compiles for the chip (``chip_smoke.py``,
``launch/train.py``, ``launch/serve.py``, ``benchmarks/run.py``) calls
``enable_compile_cache()`` before its first compile, so repeated runs
from one checkout reuse compiled programs instead of paying for them
again.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and it is
left alone.  Otherwise the cache sits at ``<checkout>/.jax_cache`` — a
fixed path, because the path is part of the cache's key: a directory
named after a pid, a time or a temporary name would never hit.
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the repository checkout this module was imported from
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its
    directory.  Every compiled program is kept, however quick its
    compile: the relational kernels each compile in about a second."""
    path = os.environ.get(_ENV)
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
