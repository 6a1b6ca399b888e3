"""Where compiled programs persist between processes.

JAX's persistent compilation cache is keyed by the directory it lives in, so
the directory must not move between runs: a path built from a temporary
name, a pid or the time never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache before the first compile and
    return its directory.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own
    handling of that variable stands and nothing is set here; otherwise the
    cache is the fixed ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
