"""Placement of JAX's persistent compilation cache (utils/compile_cache.py)."""
import os

import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_repo_cache_when_env_unset(monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    path = compile_cache.use_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_env_cache_sets_nothing(monkeypatch, tmp_path, cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
