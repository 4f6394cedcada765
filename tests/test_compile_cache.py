"""Where the persistent compile cache goes."""
import pathlib

import jax

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent


def _restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    return lambda: jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_dir_without_env(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    restore = _restore_cache_dir()
    try:
        used = compile_cache.enable_compile_cache()
        assert used == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == used
    finally:
        restore()
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_cpu_leaves_cache_off(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "cpu")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
