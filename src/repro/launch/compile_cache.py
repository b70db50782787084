"""JAX's persistent compilation cache: one location policy for every entry
point that runs on the chip (``chip_smoke.py``, ``repro.launch.serve``).

The cache key includes the directory, so the directory must not move
between runs: ``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise the
fixed ``.jax_cache/`` at the root of the checkout (gitignored) -- never a
name made from a temp dir, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
