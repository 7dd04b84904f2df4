"""Persistent XLA compilation cache location, shared by every entry point.

A cold TPU run spends most of its set-up compiling (the update program of
a deployment-sized arena alone takes tens of seconds).  JAX keys cached
programs by, among other things, the cache directory, so the directory
must not move between runs: it is either what ``JAX_COMPILATION_CACHE_DIR``
names or ``<repo>/.jax_cache``, never a temporary, per-process or
time-stamped path.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  A set ``JAX_COMPILATION_CACHE_DIR`` wins and
    is left to JAX untouched; otherwise the cache goes to
    ``<repo>/.jax_cache``.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
