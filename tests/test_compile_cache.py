"""The compile-cache helper: the environment's directory, else one fixed
directory inside the checkout."""

import os

import jax

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_honours_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory of its own.
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == os.path.join(REPO, ".jax_cache")
    assert compile_cache.compile_cache_dir() == first
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
