"""The persistent compilation cache helper: an explicit
``JAX_COMPILATION_CACHE_DIR`` wins, otherwise the fixed checkout path."""

from pathlib import Path

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = Path(__file__).resolve().parents[1] / ".jax_cache"
    assert compile_cache.CACHE_DIR == fixed
    assert compile_cache.enable_compilation_cache() == str(fixed)
    assert jax.config.jax_compilation_cache_dir == str(fixed)
