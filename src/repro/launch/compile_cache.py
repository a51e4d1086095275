"""Where JAX's persistent compilation cache lives.

A compiled program is found again only under the same cache path, so the path
is fixed: the directory named by ``JAX_COMPILATION_CACHE_DIR`` when it is set
(JAX reads the variable itself), otherwise ``.jax_cache/`` at the repository
root.  Never a temporary, pid- or time-derived directory.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
