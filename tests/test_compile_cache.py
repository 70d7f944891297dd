"""Persistent compile-cache placement (launch/compile_cache.py).

The tests never turn the cache on: with ``JAX_COMPILATION_CACHE_DIR``
set, the helper must leave JAX's configuration alone, and the default
location is checked as a path only.
"""

from pathlib import Path

import jax

from repro.launch import compile_cache


def test_env_var_wins_and_no_other_path_is_set(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == (str(tmp_path), False)
    assert jax.config.jax_compilation_cache_dir == before
    (tmp_path / "entry").write_bytes(b"x")
    assert compile_cache.enable_compile_cache() == (str(tmp_path), True)


def test_default_dir_is_the_repo_ignored_path():
    repo = Path(__file__).resolve().parents[1]
    assert compile_cache.REPO_CACHE_DIR == repo / ".jax-comp-cache"
    ignored = (repo / ".gitignore").read_text().splitlines()
    assert ".jax-comp-cache/" in ignored
