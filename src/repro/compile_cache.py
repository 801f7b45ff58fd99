"""JAX's persistent compilation cache, in one place for every entry point.

`enable()` is called by `chip_smoke.py`, `benchmarks/run.py`,
`python -m repro.net.server` and `python -m repro.launch.serve`. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and the cache
lives there: no other path is set in code. Otherwise the cache lives in
`.jax_cache/` at the root of the checkout (git ignores it). The path is
fixed, never built from a temp name, a process id or the time, because
the directory is part of what makes a cached program found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV)
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
