"""The program's compile listener (``_private/compile_cache``): a
``jax.monitoring`` listener that records one ``jax.compile`` span for each
compile and each load from the cache, those under a second included."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from ray_tpu._private import compile_cache
from ray_tpu.util import tracing


@pytest.fixture
def spans(monkeypatch):
    events: list[dict] = []
    monkeypatch.setattr(tracing, "_emit", events.append)
    return events


def test_install_is_idempotent():
    compile_cache.install_listener()
    compile_cache.install_listener()
    import ray_tpu.models  # noqa: F401  (installs it too)

    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(compile_cache._on_duration) == 1


def test_fresh_jit_is_one_miss_and_a_second_call_is_nothing(spans):
    compile_cache.install_listener()

    @jax.jit
    def fresh_program_for_the_counter(x):
        return jnp.tanh(x) * 3.0 + 1.25

    x = jnp.arange(7.0).block_until_ready()
    del spans[:]
    fresh_program_for_the_counter(x).block_until_ready()
    (ev,) = spans
    assert ev["name"] == "jax.compile"
    assert ev["attributes"]["cache"] == "miss"
    assert "fresh_program_for_the_counter" in ev["attributes"]["fun"]
    assert ev["attributes"]["seconds"] > 0
    assert ev["end"] - ev["start"] == pytest.approx(
        ev["attributes"]["seconds"])

    fresh_program_for_the_counter(x).block_until_ready()
    assert len(spans) == 1


def test_a_retrieval_marks_the_compile_that_follows_as_a_hit(spans):
    """What jax reports on a persistent-cache hit, in its order: the
    retrieval, then the whole of backend_compile on the same thread."""
    compile_cache._on_duration(compile_cache.CACHE_RETRIEVAL, 0.25)
    compile_cache._on_duration(compile_cache.BACKEND_COMPILE, 0.5,
                               fun_name="jit(train_step)")
    compile_cache._on_duration(compile_cache.BACKEND_COMPILE, 2.0,
                               fun_name="jit(other)")
    compile_cache._on_duration("/jax/some/other/event", 9.0)
    assert [(e["attributes"]["cache"], e["attributes"]["fun"],
             e["attributes"]["seconds"])
            for e in spans] == [("hit", "jit(train_step)", 0.5),
                                ("miss", "jit(other)", 2.0)]
