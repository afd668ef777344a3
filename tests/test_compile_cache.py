"""Where the entry points keep JAX's persistent compilation cache."""

import jax

from repro.launch import compile_cache


def test_cache_dir_from_env_is_left_to_jax(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself; nothing is set in code."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        where = compile_cache.enable_compile_cache()
        assert where == str(compile_cache.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == where
        assert (compile_cache.CHECKOUT_CACHE.parent / "chip_smoke.py").is_file()
        assert compile_cache.CHECKOUT_CACHE.name == ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
