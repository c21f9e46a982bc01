"""Where the entry points keep JAX's persistent compilation cache."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # JAX reads the variable into its config when it starts
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_the_checkout_cache_on_every_call(monkeypatch,
                                                     cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".jax_cache")
    for _ in range(2):
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want


def test_checkout_cache_is_ignored_by_git():
    ignored = (CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
