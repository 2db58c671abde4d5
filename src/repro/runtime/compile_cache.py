"""JAX's persistent compilation cache, kept in one fixed place.

A cold process compiles every kernel and jitted graph it touches; with the
cache on, a later process on the same machine reads them back instead.
Entry points (chip_smoke.py, benchmarks/run.py) call `enable_compile_cache`
before their first compile.

Where the cache lives: the directory in JAX_COMPILATION_CACHE_DIR when that
variable is set (JAX reads it itself, so nothing is set in code), otherwise
`<repo>/.jax_cache`.  The path is fixed — never derived from a temporary
directory, a pid or the time — so the next process finds what this one
wrote.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Every compiled program is cached, however fast it compiled: the
    serving path is many sub-second compiles (one per kernel and shape
    bucket), which JAX's default one-second floor would all skip."""
    import jax

    directory = os.environ.get(ENV_VAR)
    if not directory:
        directory = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory
