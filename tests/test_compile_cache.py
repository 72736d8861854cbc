"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.compile_cache import CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_ignored_dir_at_the_checkout_root(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CACHE_DIR == ROOT / ".jax_cache"
    assert enable_compile_cache() == str(CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_import_repro_sets_no_cache_dir():
    # JAX itself reads the variable; repro adds nothing on import
    assert (jax.config.jax_compilation_cache_dir or None) == (
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or None)
