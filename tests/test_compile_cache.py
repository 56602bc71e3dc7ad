"""The entry points' persistent compile cache (repro.launch.compile_cache)."""
import os

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_env_dir_is_used_exactly(monkeypatch, tmp_path,
                                 restore_cache_config):
    want = str(tmp_path / "xla-cache")
    monkeypatch.setenv(compile_cache.ENV_VAR, want)
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == \
        compile_cache.MIN_COMPILE_S < 1.0


def test_default_dir_is_one_fixed_path_in_the_checkout(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
