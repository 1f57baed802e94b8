"""Where JAX's persistent compilation cache lives.

Every entry point (`launch/serve.py`, `chip_smoke.py`, `benchmarks/run.py`)
calls `enable_compile_cache()` once before its first compile. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing is
set here: the environment decides. Otherwise the cache is
`<checkout>/.jax_cache` (listed in `.gitignore`): a fixed path, because
the directory is part of what a later process must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory. At the fixed path every compile is kept (no
    minimum compile time): a full-width step compiles for the TPU in about
    a second, at JAX's default threshold."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
