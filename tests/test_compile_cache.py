"""Placement of JAX's persistent compilation cache (repro.launch.cache).

The cache key includes its directory, so the helper must never move it: the
environment's ``JAX_COMPILATION_CACHE_DIR`` is left alone, and without it
the cache lives at one fixed path inside the checkout. ``jax.config.update``
is replaced by a recorder, so these tests never redirect the real cache.
"""

import pathlib

import jax
import pytest

from repro.launch import cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def updates(monkeypatch):
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    return seen


def test_env_dir_is_left_to_jax(monkeypatch, updates):
    monkeypatch.setenv(cache.CACHE_ENV, "/somewhere/shared/jax-cache")
    assert cache.configure_compilation_cache() == "/somewhere/shared/jax-cache"
    assert updates == []          # no second cache is configured


def test_unset_env_uses_fixed_checkout_dir(monkeypatch, updates):
    monkeypatch.delenv(cache.CACHE_ENV, raising=False)
    first = cache.configure_compilation_cache()
    second = cache.configure_compilation_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", first)] * 2


def test_empty_env_counts_as_unset(monkeypatch, updates):
    monkeypatch.setenv(cache.CACHE_ENV, "")
    assert cache.configure_compilation_cache() == str(REPO / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]


def test_checkout_cache_dir_is_gitignored():
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
