"""enable_compile_cache: the environment wins, else one fixed in-checkout
directory."""
import pathlib

import jax
import pytest

from repro.serve import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_inside_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    path = pathlib.Path(first)
    assert path.name == ".jax_cache"
    assert (path.parent / "pyproject.toml").is_file()
    ignored = (path.parent / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
