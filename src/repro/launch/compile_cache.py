"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (`chip_smoke.py`, `launch/train.py`,
`launch/serve.py`): when `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and nothing here overrides it; otherwise the cache lives at the fixed
path `<checkout>/.jax_cache` (git-ignored).  The path is fixed, never a temp
name, pid or time, because a cache that moves is never hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory.  Call before the first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
