"""The persistent compile cache helper that the entry points call."""
import jax

from repro import compile_cache


def _restore(saved):
    for name, value in saved.items():
        jax.config.update(name, value)


def _config(*names):
    return {n: getattr(jax.config, n) for n in names}


NAMES = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
)


def test_default_dir_is_fixed_under_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    saved = _config(*NAMES)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.DEFAULT_CACHE_DIR)
        assert compile_cache.DEFAULT_CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.DEFAULT_CACHE_DIR.parent / "src").is_dir()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        _restore(saved)


def test_env_dir_is_left_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    saved = _config(*NAMES)
    try:
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
    finally:
        _restore(saved)
