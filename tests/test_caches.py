"""Where the compile cache and the autotune table live (``repro.caches``)."""

from pathlib import Path

import jax
import pytest

from repro import caches
from repro.kernels import autotune

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def jax_cache_dir():
    """Restore JAX's compile-cache directory after a test changes it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_honoured_and_nothing_else_is_set(
    monkeypatch, tmp_path, jax_cache_dir
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert caches.enable_compile_cache() == tmp_path
    assert caches.compile_cache_dir() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, jax_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = caches.enable_compile_cache()
    assert path == REPO / ".cache" / "jax"
    assert caches.compile_cache_dir() == path           # same on every call
    assert jax.config.jax_compilation_cache_dir == str(path)


def test_autotune_table_defaults_beside_the_compile_cache(monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert autotune.cache_path() == REPO / ".cache" / "autotune.json"


def test_cache_root_is_git_ignored():
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".cache/" in ignored
