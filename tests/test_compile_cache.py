"""Where the persistent compilation cache goes. These tests never turn
the cache on: ``jax.config.update`` is replaced by a recorder."""
import os

import jax
import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_unset_env_uses_the_repo_cache(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compilation_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compilation_cache() == str(tmp_path)
    assert updates == []


def test_repo_cache_is_gitignored_and_import_is_inert():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # importing the module (done above) turned nothing on
    assert jax.config.jax_compilation_cache_dir in (
        None, os.environ.get("JAX_COMPILATION_CACHE_DIR"))
