"""Where the persistent compilation cache goes."""

import jax

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache


def _restoring(fn):
    was = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_set_from_outside_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def run():
        before = jax.config.jax_compilation_cache_dir
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    _restoring(run)


def test_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def run():
        path = use_compile_cache()
        assert path == str(CHECKOUT_CACHE_DIR) == jax.config.jax_compilation_cache_dir
        assert CHECKOUT_CACHE_DIR.name == ".jax_cache"
        assert (CHECKOUT_CACHE_DIR.parent / "src" / "repro").is_dir()
        assert use_compile_cache() == path  # the same on every call

    _restoring(run)
