"""The entry points' persistent compilation cache follows
``JAX_COMPILATION_CACHE_DIR`` where it is set, else a fixed directory at the
checkout root."""

from pathlib import Path

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_fixed_directory_at_the_checkout_root(monkeypatch,
                                               restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert path == str(root / ".jax_cache") == cache.CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_environment_directory_is_left_to_jax(monkeypatch, tmp_path,
                                              restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # not overridden
