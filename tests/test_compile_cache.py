"""The persistent compilation cache lives where the environment says.

`JAX_COMPILATION_CACHE_DIR`, when set, is the directory (and compiled
programs are written there); otherwise the cache is the fixed
`<repo>/.jax_cache`.  Each test restores JAX's cache settings, so the
rest of the worker's tests run as before.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.compile_cache import DEFAULT_DIR, ENV_VAR, use_compile_cache

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    saved = {
        name: getattr(jax.config, name)
        for name in ("jax_compilation_cache_dir",
                     "jax_persistent_cache_min_compile_time_secs")
    }
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    cc.reset_cache()


def test_cache_dir_follows_env_and_receives_entries(
        monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.125)(jnp.arange(7.0))
    assert any(tmp_path.iterdir()), "no cache entry written"


def test_cache_dir_defaults_to_repo(monkeypatch, restore_cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert DEFAULT_DIR == REPO_ROOT / ".jax_cache"
    assert use_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
