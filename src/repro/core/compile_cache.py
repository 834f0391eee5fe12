"""JAX's persistent compilation cache at a fixed path.

A chip run compiles every detector bucket, window size and kernel it
meets; a persistent cache turns a second run's compiles into reads.  The
cache key includes the directory, so the directory must not move from
run to run.  Entry points that run on the chip (``chip_smoke.py`` and the
benchmark ``main``s) call ``enable_compile_cache`` once at start-up; it
is never called at import and never from tests.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory from the environment and nothing is set here.  Otherwise
    the cache goes to ``<repo>/.jax_cache`` (gitignored), and every
    compile is kept, however short, since a cold chip run is made of
    many sub-second compiles."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(DEFAULT_DIR)
