"""The entry points' compile-cache location."""
import os

import jax

from conftest import REPO
from repro.launch import compile_cache


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    calls = _record_updates(monkeypatch)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_fallback_is_one_fixed_path_inside_the_repo(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    calls = _record_updates(monkeypatch)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert compile_cache.cache_dir() == path


def test_fallback_dir_is_gitignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
