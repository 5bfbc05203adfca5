"""JAX's persistent compilation cache for the launchers.

Called from the ``main()`` of each entry point (``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train``), never at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
path, so every process started from the same checkout finds what an
earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
