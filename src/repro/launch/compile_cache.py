"""Where JAX keeps its persistent compilation cache.

A cold chip run spends much of its time compiling, so every entry point
(`launch/serve.py`, `launch/train.py`, `chip_smoke.py`) turns the cache
on through `enable_compile_cache` before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  nothing is set in code, so whoever runs the program places the cache.
* unset: the cache goes to ``<repo>/.jax_cache`` (gitignored). The
  directory is part of what a later run must find again, so it is a
  fixed path in the checkout — never a temp, pid- or time-named one.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return compile_cache_dir()
