"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` first thing in ``main``;
importing this module changes nothing.  The path is part of the cache's
key, so it is fixed: a directory that moved between runs would never
hit.
"""

from __future__ import annotations

import os
from pathlib import Path

#: src/repro/launch/compile_cache.py -> the checkout is three parents up
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself);
    otherwise ``<checkout>/.jax_cache``.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
