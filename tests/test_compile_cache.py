"""The persistent compilation cache lands where the environment says, or at
one fixed place in the checkout — never a temp, pid- or time-keyed path."""
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.runtime import compile_cache


@pytest.fixture()
def restore_cache_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.use_compile_cache() == want  # same path every call
