"""Placement of JAX's persistent compilation cache (launch/compile_cache)."""

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_nothing_else_is_set(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert first == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.parent.joinpath("src", "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == first
