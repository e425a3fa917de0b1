"""The entry points' persistent compilation cache directory."""
import jax

from repro import compile_cache


def test_respects_env_and_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_falls_back_to_fixed_repo_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.setup_compile_cache()
        assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.setup_compile_cache() == path  # fixed, stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (compile_cache.REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
