"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points that run on the chip (``chip_smoke.py``, ``benchmarks/run.py``)
call :func:`use_compile_cache` once, before their first compilation; the
tests never do. The cache's path is part of its key, so it is fixed: the
directory that ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads
that variable itself), and otherwise ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
