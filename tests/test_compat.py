"""Kernel backend default and the compile-cache placement helper."""
import jax

from repro.kernels.compat import auto_interpret, resolve_interpret
from repro.launch import compile_cache
from repro.launch.compile_cache import CACHE_ENV, compile_cache_dir


def test_auto_interpret_on_cpu():
    # this container has no TPU: the default must be interpret mode
    assert jax.default_backend() != "tpu"
    assert auto_interpret() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False


def test_compile_cache_dir_env_wins_else_fixed_repo_path(monkeypatch, tmp_path):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(CACHE_ENV)
    first = compile_cache_dir()
    assert first == compile_cache_dir() == str(compile_cache.DEFAULT_CACHE_DIR)
    repo = compile_cache.DEFAULT_CACHE_DIR.parent
    assert (repo / "src" / "repro" / "launch" / "compile_cache.py").exists()
    gitignore = (repo / ".gitignore").read_text().split()
    assert "/.jax_cache/" in gitignore

    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
