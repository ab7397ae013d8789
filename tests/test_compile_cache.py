"""The persistent compile cache: placed from outside, else a fixed path."""

from __future__ import annotations

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_dir_is_left_to_jax(monkeypatch, restore_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_in_checkout(monkeypatch, restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == compile_cache.DEFAULT_DIR
    assert path.endswith("/.jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call, in every process
    assert compile_cache.use_compile_cache() == path
