"""One persistent compilation cache, placed from outside the program."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_dir_in_the_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.CHECKOUT_CACHE_DIR.parent.joinpath(
        "chip_smoke.py").is_file()
    assert compile_cache.enable_compile_cache() == path    # same every call
