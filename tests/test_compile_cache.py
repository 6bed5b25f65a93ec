"""The entry points' persistent compilation cache sits at a fixed path:
where JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache."""

import os

import jax
import pytest

from repro.compile_cache import setup_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the process's cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/srv/jax-cache")
    assert setup_compile_cache() == "/srv/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_the_checkout_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = setup_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert setup_compile_cache() == path        # the same on every call
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
