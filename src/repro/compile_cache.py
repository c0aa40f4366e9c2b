"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `python -m benchmarks.run`,
`python -m repro.explore`) call `configure_compile_cache()` once, before
their first compile; importing the library itself changes nothing.

If `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this sets
nothing.  Otherwise the cache lives at the fixed `<checkout>/.jax_cache`
— never a path built from a temp name, a pid or the time, because the
directory is part of what makes a later run find the entry again.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
