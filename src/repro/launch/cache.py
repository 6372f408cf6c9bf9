"""Where compiled programs are cached between runs.

Every entry point (the launchers, the dry-run and perf CLIs, the tools and
`chip_smoke.py`) calls `use_compile_cache()` before its first compile. JAX
keys a cache entry by, among other things, the directory it lives in, so the
directory must not move between runs: no temporary, per-process or dated
paths.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (gitignored): this file is src/repro/launch/cache.py.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing is
    set here. Otherwise the cache goes to the checkout's `.jax_cache/`."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
