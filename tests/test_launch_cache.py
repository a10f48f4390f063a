"""The entry points' persistent compilation cache location."""
from pathlib import Path

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache setting after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compilation_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = cache.enable_compilation_cache()
    root = Path(__file__).resolve().parents[1]
    assert got == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # the same path on every call: the directory is part of the cache key
    assert cache.enable_compilation_cache() == got
