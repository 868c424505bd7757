"""The persistent compilation cache lands where the entry points say:
``$JAX_COMPILATION_CACHE_DIR`` when set (nothing in the repo overrides
it), else ``<checkout>/.jax_cache``."""
import importlib
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_DIR, use_compile_cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_default_cache_is_in_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == DEFAULT_DIR
    assert Path(DEFAULT_DIR) == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_env_cache_dir_is_left_alone(monkeypatch, tmp_path,
                                     cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_importing_the_entry_points_sets_no_cache(cache_dir_config):
    import repro.launch.gnn_serve
    import repro.launch.train
    before = jax.config.jax_compilation_cache_dir
    for mod in (repro.launch.gnn_serve, repro.launch.train):
        importlib.reload(mod)
    assert jax.config.jax_compilation_cache_dir == before
