"""JAX's persistent compilation cache, shared by every entry point.

``serve``, ``train``, ``compile_plans`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before they compile anything, so a process
that compiles the same program as an earlier one (on the same chip and
installation) loads it instead of compiling again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at a fixed path inside the
checkout, ``<repo>/.jax_cache`` (gitignored): the directory is part of what
a later run must find again, so it never depends on a temporary name, a
process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache uses: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else the checkout's ``.jax_cache``."""
    return os.environ.get(ENV) or str(REPO_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent cache on (before the first compile) and return
    its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
