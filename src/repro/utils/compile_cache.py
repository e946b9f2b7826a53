"""Where JAX's persistent compilation cache lives.

Entry points (the chip smoke run, the benchmark CLI, the serving worker and
launcher) call `use_compile_cache()` before they compile anything, so a
restarted process, or the next process of the same run, reads back what an
earlier one compiled instead of compiling it again.

The placement is decided from outside: when ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here.  Otherwise the cache
goes to ``<repo>/.jax_cache``, a fixed path (gitignored).  The directory is
part of what a later process must find again, so it is never a temporary,
per-process or time-stamped one.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
