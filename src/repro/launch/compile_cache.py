"""JAX's persistent compilation cache for the command-line entry points.

`enable_compile_cache()` is called from the `main()`s (serve, train,
explore, `benchmarks/run.py`) and from `chip_smoke.py`, never at import,
so tests and library callers keep JAX's defaults.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed; otherwise the cache lives at one fixed, git-ignored path inside
the checkout (``<repo>/.jax_cache``).  A later run finds what an earlier
one compiled only at the same path, so it never depends on a temp dir, a
pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
