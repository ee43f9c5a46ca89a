"""Where the entry points put JAX's persistent compilation cache."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache

CHECKOUT_CACHE = Path(__file__).resolve().parents[1] / ".jax_cache"


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path,
                                            cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == str(CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.enable_compile_cache() == first
