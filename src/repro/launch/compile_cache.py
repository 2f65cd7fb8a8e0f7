"""JAX's persistent compilation cache for the repo's command-line entry points.

A chip run pays for every compile, and the path of the cache is part of
what lets a later run find an entry, so the cache lives at one fixed place:
the directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads
that variable itself, so nothing is set here), and ``<repo>/.jax_cache``
otherwise. Library code and tests never turn it on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
