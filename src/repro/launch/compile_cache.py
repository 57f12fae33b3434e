"""JAX's persistent compilation cache, placed from outside the program.

`enable()` is called by the entry points (`repro.launch.train.main`, the
benchmark scripts, `chip_smoke.py`), never at import. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads that variable itself and no
other directory is set here. Otherwise the cache lives at the fixed path
`<checkout>/.jax_cache`: the directory is part of the cache key, so a path
that moved between runs (a temp dir, a pid or a timestamp) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    import jax

    # cache every program, kernels included: a cold call pays for them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
