"""Persistent XLA compilation cache for the entry points.

A cold process compiles every program it runs; on an accelerator host the
tuning service's chunk programs take seconds each.  JAX can keep compiled
executables on disk and find them again in the next process, but only if
the cache directory is the same every time (the path is part of the key).

`enable_compile_cache` is called by the entry points — `chip_smoke.py`
and `benchmarks/run.py` — and never by a library import or by the tests:

  * when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here;
  * otherwise the cache goes to the fixed ``.jax_cache/`` directory at the
    repository root (listed in ``.gitignore``).
"""

import os

import jax

REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
