"""Where JAX keeps its persistent compilation cache.

Called from the entry points (``chip_smoke.py``, ``launch/serve_engine.py``,
``benchmarks/serve_throughput.py``), never at import.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets no
other directory.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path (the path is part of the cache key, so a directory made from a
temporary name, pid or time would never hit), git-ignored.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache(checkout: Path = CHECKOUT) -> str:
    """Point the persistent compilation cache at its one directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
