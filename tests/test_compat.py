"""Compile-cache placement (repro.core.compat.enable_compile_cache)."""
import jax

from repro.core import compat


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compat.COMPILE_CACHE_ENV, str(tmp_path))
    assert compat.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads env


def test_compile_cache_defaults_to_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compat.COMPILE_CACHE_ENV, raising=False)
    try:
        path = compat.enable_compile_cache()
        assert path == str(compat.CHECKOUT / ".jax_cache")
        assert (compat.CHECKOUT / "chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
