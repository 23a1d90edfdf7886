"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``launch/attach_server.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` once, before they
compile anything. A cache directory set from outside through
``JAX_COMPILATION_CACHE_DIR`` wins, and nothing else is set. Otherwise
the cache lives at a fixed ``<checkout>/.jax_cache``: the path is part of
each entry's key, so a directory named after a temp name, a pid or the
time would never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    outside = os.environ.get(ENV)
    if outside:
        return outside          # JAX reads the variable itself
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
