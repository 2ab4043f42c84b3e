"""The persistent compilation cache's directory rule: JAX's own
environment variable is honoured untouched; without it the cache lives at
one fixed path inside the checkout — the directory is part of the cache's
key, so a temporary or per-run path would never hit."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture()
def saved_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, saved_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
    assert compile_cache.enable_compile_cache() == tmp_path / "cc"
    # nothing set in code: JAX read the variable itself at import
    assert jax.config.jax_compilation_cache_dir == saved_cache_dir


def test_fixed_checkout_path_without_env(monkeypatch, saved_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == CHECKOUT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(first)
    assert compile_cache.enable_compile_cache() == first
    assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()
