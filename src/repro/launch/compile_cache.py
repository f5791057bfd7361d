"""JAX's persistent compilation cache, switched on by every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and is left
alone. Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed path,
because the path is part of what makes a later run find an entry. Library
code and tests never call this; only the ``__main__`` blocks of the entry
points (`launch.serve`, `launch.train`, `benchmarks/run.py`,
`chip_smoke.py`) do.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
