"""``enable_compile_cache``: a fixed path in the checkout, or JAX's own
``JAX_COMPILATION_CACHE_DIR`` untouched."""

import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_cache_dir(monkeypatch, restore_cache_dir, env_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    compile_cache.enable_compile_cache()
    got = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        assert got == str(compile_cache.CACHE_DIR)
        assert compile_cache.CACHE_DIR.parent.joinpath("pyproject.toml").exists()
    else:
        assert got is None  # JAX reads the variable itself
