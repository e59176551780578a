"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `launch/serve.py`, `benchmarks/run.py`,
`experiments/run.py`) call `use_compile_cache()` first thing in `main`;
importing this module sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
    itself and nothing is set here; otherwise the cache is
    `<repo>/.jax_cache`, a fixed path so that later runs find it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
