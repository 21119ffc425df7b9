"""The compile cache can be placed from outside, and is in one place.

``JAX_COMPILATION_CACHE_DIR`` set: every worker's entries land there and
no code sets another directory. Unset: ``<checkout>/.jax_cache`` — a
fixed path, because the path is part of the cache key.
"""

from __future__ import annotations

import os
import random

import pytest

import ray_tpu
from ray_tpu._private import compile_cache

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def _listing(path: str) -> set:
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def _jit_in_a_worker(salt: float):
    """A chip-less `tiny` jit nobody has compiled before (the salt is
    baked into the program), small enough to be below jax's default
    caching thresholds — lowered here, not redirected."""

    @ray_tpu.remote
    def jit_once(salt):
        import jax
        import jax.numpy as jnp

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.jit(lambda x: x * salt + 1.0)(jnp.arange(8.0)).block_until_ready()
        return (os.environ.get("JAX_COMPILATION_CACHE_DIR"),
                jax.config.jax_compilation_cache_dir)

    return ray_tpu.get(jit_once.remote(salt), timeout=120)


@pytest.fixture
def fresh_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


def test_helper_prefers_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.compile_cache_dir() == CHECKOUT_CACHE
    # Called twice, a fixed path twice: no pid, session id or time in it.
    assert compile_cache.compile_cache_dir() == CHECKOUT_CACHE


def test_env_dir_takes_every_entry(monkeypatch, tmp_path, fresh_cluster):
    target = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    before_default = _listing(CHECKOUT_CACHE)
    ray_tpu.init(num_cpus=2, object_store_memory=32 * 1024 * 1024)
    env_dir, jax_dir = _jit_in_a_worker(random.random())
    assert env_dir == jax_dir == str(target)
    assert _listing(str(target)), "no entry written where the variable says"
    assert _listing(CHECKOUT_CACHE) == before_default


def test_default_dir_is_the_checkouts(monkeypatch, fresh_cluster):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = _listing(CHECKOUT_CACHE)
    ray_tpu.init(num_cpus=2, object_store_memory=32 * 1024 * 1024)
    try:
        env_dir, jax_dir = _jit_in_a_worker(random.random())
        assert env_dir == jax_dir == CHECKOUT_CACHE
        new = _listing(CHECKOUT_CACHE) - before
        assert new, "no entry written under <checkout>/.jax_cache"
    finally:
        for name in _listing(CHECKOUT_CACHE) - before:
            os.remove(os.path.join(CHECKOUT_CACHE, name))
