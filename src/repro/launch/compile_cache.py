"""Persistent XLA compilation cache for the entry points.

A cold process compiles every kernel and serving program again; JAX's
persistent cache keeps the compiled executables on disk so a later run
of the same programs loads them instead. The entry points
(``launch/serve.py``, ``examples/serve_bebr.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call ``enable_compile_cache`` once, before their
first compile; importing this module changes nothing.

Placement: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module sets no other path. Otherwise the cache lives at
``<repo>/.jax-comp-cache`` (ignored by git), resolved from this file's
location — a fixed path, since the path is part of what makes a later
run find the entries.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax-comp-cache"


def enable_compile_cache() -> tuple[str, bool]:
    """Turn the persistent cache on; returns (directory, warm).

    ``warm`` is True when the directory already held entries, i.e. this
    run can load compiled programs instead of compiling them.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    warm = os.path.isdir(path) and any(os.scandir(path))
    return path, warm
