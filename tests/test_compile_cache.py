"""The compile-cache rule: the environment's directory, else one fixed
path inside the checkout."""
from pathlib import Path

import jax

from repro.launch import compile_cache


def test_env_directory_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before


def test_default_directory_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first, second = compile_cache.cache_dir(), compile_cache.cache_dir()
    assert first == second == compile_cache.DEFAULT_DIR
    root = Path(__file__).resolve().parent.parent
    assert first == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.configure() == first
        assert jax.config.jax_compilation_cache_dir == str(first)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
