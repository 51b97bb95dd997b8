"""The entry points' compile-cache placement (``launch/compile_cache.py``)."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.setup_compile_cache()
    repo = Path(__file__).resolve().parents[1]
    assert got == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.setup_compile_cache() == got  # stable across calls
