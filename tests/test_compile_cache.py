"""Where the persistent compilation cache goes."""
import jax

from repro import compile_cache


def _restore(previous):
    jax.config.update("jax_compilation_cache_dir", previous)


def test_environment_variable_wins(monkeypatch, tmp_path):
    previous = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets no other directory
        assert jax.config.jax_compilation_cache_dir == previous
    finally:
        _restore(previous)


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    previous = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path    # stable
    finally:
        _restore(previous)
    repo = compile_cache.DEFAULT_DIR.parent
    assert (repo / "pyproject.toml").exists()
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
