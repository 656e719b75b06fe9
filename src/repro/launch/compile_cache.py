"""Persistent XLA compilation cache for the launch drivers and chip_smoke.py.

A served step at RMAT-20 takes tens of seconds to compile, and every process
starts cold unless it finds the executables on disk. `enable()` turns jax's
persistent cache on at a directory that a later run finds again:

* `JAX_COMPILATION_CACHE_DIR`, when it is set (jax reads it itself; nothing
  else is set), so the machine's owner decides where the cache lives;
* otherwise `<checkout>/.jax_cache`, a fixed path resolved from this file's
  location (the path is part of what makes a later run hit), which
  `.gitignore` lists.

Drivers call it from `main()`, never at import, so tests run without a cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory `enable()` uses: the environment's, else the checkout's."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
