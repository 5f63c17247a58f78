"""The persistent compilation cache sits at a fixed path unless
``JAX_COMPILATION_CACHE_DIR`` places it from outside."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_default_dir_is_fixed_under_repo(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.DEFAULT_DIR.parts[-2:] == (".cache", "jax")
    assert (compile_cache.DEFAULT_DIR.parents[1] / "src" / "repro").is_dir()
    assert compile_cache.enable_compile_cache() == got     # same every call


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
