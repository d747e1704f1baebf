"""JAX persistent compilation cache for the entry points.

Entry points (`repro.launch.rl_train`, `chip_smoke.py`) call
`enable_compile_cache()` before their first compile; importing a module
never touches the cache.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX
already reads it and nothing else is configured.  Otherwise the cache goes
to `<checkout>/.jax_cache` — a fixed path, because the path is part of
what JAX matches when it looks an entry up.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    configured = os.environ.get(ENV_VAR)
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
