"""The entry points' persistent compilation cache directory."""

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()


def test_env_dir_is_used_as_it_is(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # nothing set in code: JAX reads the variable itself
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_env_uses_the_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
