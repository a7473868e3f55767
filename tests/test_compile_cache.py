"""The entry points' compile-cache placement (``repro.launch.compile_cache``)."""

import os

import jax
import pytest

from conftest import REPO
from repro.launch.compile_cache import DEFAULT_CACHE_DIR, use_compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    for _ in range(2):
        assert use_compile_cache() == DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
