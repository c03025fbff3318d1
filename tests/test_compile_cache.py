"""Placement of JAX's persistent compilation cache
(``repro.launch.compile_cache``)."""
import jax
import pytest

from repro.launch.compile_cache import CACHE_DIRNAME, enable_compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_nothing_is_set(cache_dir_config, monkeypatch,
                                         tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outer"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(tmp_path) == str(tmp_path / "outer")
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_under_the_checkout(cache_dir_config, monkeypatch,
                                            tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(tmp_path.resolve() / CACHE_DIRNAME)
    assert enable_compile_cache(tmp_path) == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same checkout always maps to the same directory
    assert enable_compile_cache(tmp_path) == want
