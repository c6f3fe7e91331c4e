"""`repro.launch.cache.enable_compile_cache`: the environment's cache
directory wins and nothing is set; otherwise one fixed in-checkout path."""
from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_set(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_path_otherwise(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = Path(__file__).resolve().parents[1]
    first = cache.enable_compile_cache()
    assert first == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert cache.enable_compile_cache() == first    # same path every run
