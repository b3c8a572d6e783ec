"""JAX's persistent compilation cache for the repo's entry points.

`chip_smoke.py`, `launch/serve.py` and `benchmarks/serve_bench.py` call
`setup_compile_cache()` before their first compile.  Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this sets
nothing.  Otherwise the cache goes to `<checkout>/.jax_cache` — a fixed
path, so a later run from the same checkout finds what an earlier one
compiled.  Tests never call this.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
ENV = "JAX_COMPILATION_CACHE_DIR"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
