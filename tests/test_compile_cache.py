"""Placement of JAX's persistent compilation cache for entry points."""

import os

import jax

from repro.launch import compile_cache

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_cache_dir_from_environment_sets_nothing(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.use_compile_cache() == "/somewhere/cache"
    assert updates == []


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    path = os.path.join(REPO, "artifacts", "jax_cache")
    assert compile_cache.use_compile_cache() == path
    assert compile_cache.use_compile_cache() == path   # same every run
    assert updates == [("jax_compilation_cache_dir", path)] * 2
