"""Placement of the persistent compilation cache (utils/compile_cache.py)."""
import jax
import pytest

from repro.utils.compile_cache import CHECKOUT_CACHE, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", ["", "/elsewhere/jax-cache"])
def test_cache_dir_from_env_or_checkout(monkeypatch, restore_cache_dir,
                                        env_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    got = enable_compile_cache()
    if env_dir:
        # JAX reads the variable itself; the code sets no other directory
        assert got == env_dir
        assert jax.config.jax_compilation_cache_dir == before
    else:
        assert got == str(CHECKOUT_CACHE)
        assert CHECKOUT_CACHE.name == ".jax_cache"
        assert (CHECKOUT_CACHE.parent / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == got
