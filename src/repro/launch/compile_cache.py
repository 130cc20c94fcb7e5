"""JAX's persistent compilation cache, kept at one fixed path.

A cache entry is found again only where it was written, so the directory
never comes from a temporary name, a pid or the clock. ``enable()``:

  * leaves the cache to JAX when ``JAX_COMPILATION_CACHE_DIR`` is set (JAX
    reads that variable itself; nothing is set in code);
  * otherwise points JAX at ``<checkout>/.jax_cache`` (git-ignored).

Entry points call it once, before their first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
