"""Where the CLI entry points keep JAX's persistent compilation cache.

A full-width model step takes tens of seconds to compile; the persistent
cache makes every run after the first skip that.  The cache key includes
the directory, so it must not move between runs: `$JAX_COMPILATION_CACHE_DIR`
when the environment sets it (JAX reads that variable itself, so nothing is
set here), otherwise one fixed directory inside the checkout.

Only entry points call `enable_compile_cache` (under their ``__main__``
check); importing this module sets nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
