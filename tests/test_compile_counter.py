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


def test_a_fresh_jit_carries_its_trace_and_lowering_on_the_one_span(spans):
    """What the jit cost in Python ahead of the compiler rides on the
    ``jax.compile`` span as attributes; the span's own start, end and
    ``seconds`` stay the backend's, and no second span is recorded."""
    import time

    compile_cache.install_listener()

    @jax.jit
    def fresh_program_with_a_slow_trace(x):
        time.sleep(0.05)
        return jnp.sin(x) * 2.5 - 0.75

    x = jnp.arange(5.0).block_until_ready()
    del spans[:]
    fresh_program_with_a_slow_trace(x).block_until_ready()
    (ev,) = spans
    a = ev["attributes"]
    assert a["cache"] == "miss"
    assert a["trace_s"] >= 0.05 and a["lower_s"] > 0
    assert a["lead_s"] >= a["trace_s"] + a["lower_s"]
    assert ev["end"] - ev["start"] == pytest.approx(a["seconds"])


def test_the_outermost_trace_is_the_one_charged(spans):
    """An inner jit's trace fires first and lies inside the outer's: it is
    counted once, in the outer's."""
    import time

    compile_cache.install_listener()

    @jax.jit
    def inner_that_sleeps(x):
        time.sleep(0.2)
        return x * 2.0

    @jax.jit
    def outer_around_the_inner(x):
        return inner_that_sleeps(x) + 1.0

    x = jnp.arange(6.0).block_until_ready()
    del spans[:]
    outer_around_the_inner(x).block_until_ready()
    (ev,) = [e for e in spans
             if "outer_around_the_inner" in e["attributes"]["fun"]]
    assert 0.2 <= ev["attributes"]["trace_s"] < 0.3
    assert not any("inner_that_sleeps" in e["attributes"]["fun"]
                   for e in spans)


def test_a_trace_no_compile_follows_is_charged_to_no_span(spans):
    import time

    compile_cache.install_listener()

    def slow_to_trace(x):
        time.sleep(0.2)
        return jnp.cos(x) + 3.5

    x = jnp.arange(4.0).block_until_ready()
    del spans[:]
    jax.eval_shape(slow_to_trace, x)
    assert spans == []

    @jax.jit
    def fresh_behind_an_eval_shape(x):
        return jnp.exp(x) * 0.125 + 7.0

    fresh_behind_an_eval_shape(x).block_until_ready()
    (ev,) = spans
    assert "fresh_behind_an_eval_shape" in ev["attributes"]["fun"]
    assert ev["attributes"]["trace_s"] < 0.1
    assert ev["attributes"]["lead_s"] < 0.2    # not back to the eval_shape


def test_a_hit_keeps_its_mark_and_takes_the_lead_ahead_of_it(spans):
    """jax's order on a persistent-cache hit, with the Python ahead of it:
    trace, lowering, the retrieval, then the whole of backend_compile."""
    import time

    now = time.time()
    compile_cache._on_time_span(compile_cache.TRACE, now - 4.0, now - 3.0,
                                fun_name="train_step")
    compile_cache._on_time_span(compile_cache.LOWER, now - 2.75, now - 1.25,
                                fun_name="jit(train_step)")
    compile_cache._on_duration(compile_cache.CACHE_RETRIEVAL, 0.25)
    compile_cache._on_duration(compile_cache.BACKEND_COMPILE, 0.5,
                               fun_name="jit(train_step)")
    compile_cache._on_duration(compile_cache.BACKEND_COMPILE, 2.0,
                               fun_name="jit(other)")
    hit, bare = (e["attributes"] for e in spans)
    assert (hit["cache"], hit["seconds"]) == ("hit", 0.5)
    assert hit["trace_s"] == pytest.approx(1.0)
    assert hit["lower_s"] == pytest.approx(1.5)
    assert hit["lead_s"] == pytest.approx(3.5, abs=0.05)   # 4.0 - 0.5
    assert (bare["cache"], bare["trace_s"], bare["lower_s"],
            bare["lead_s"]) == ("miss", 0.0, 0.0, 0.0)


def test_a_trace_made_while_lowering_does_not_take_the_jits_own_place(spans):
    """A lowering rule that calls a jitted helper traces it: that trace
    fires after the jit's own and before its LOWER. jax announces the
    lowering's start, and what is traced from then on is the lowering's."""
    import time

    now = time.time()

    def trace(start, end):
        compile_cache._on_time_span(compile_cache.TRACE, now + start,
                                    now + end)

    trace(-9.0, -8.0)       # an eval_shape long before
    trace(-5.5, -5.25)      # an inner jit, inside the jit's own
    trace(-6.0, -5.0)       # the jit's own
    compile_cache._on_scalar(compile_cache.LOWER, now - 4.75,
                             fun_name="jit(step)")
    trace(-4.5, -4.25)      # helpers of lowering rules
    trace(-4.0, -3.5)
    compile_cache._on_time_span(compile_cache.LOWER, now - 4.75, now - 3.0)
    compile_cache._on_duration(compile_cache.BACKEND_COMPILE, 0.5,
                               fun_name="jit(step)")
    trace(-0.2, -0.1)       # the next jit's: lowering is over
    assert compile_cache._pending.trace == (now - 0.2, now - 0.1)
    compile_cache._pending.trace = None
    (ev,) = spans
    assert ev["attributes"]["trace_s"] == pytest.approx(1.0)
    assert ev["attributes"]["lower_s"] == pytest.approx(1.75)
    assert ev["attributes"]["lead_s"] == pytest.approx(5.5, abs=0.05)


def test_a_real_step_with_random_bits_keeps_its_own_trace(spans):
    """The same on a real jit: ``jax.random`` lowers through jitted
    helpers, and the body's 0.2 s of tracing are still the span's."""
    import time

    compile_cache.install_listener()

    @jax.jit
    def draws_while_it_lowers(key, x):
        time.sleep(0.2)
        return x + jax.random.normal(key, x.shape) * jnp.float32(0.5)

    key = jax.random.PRNGKey(3)
    x = jnp.arange(12.0).block_until_ready()
    del spans[:]
    draws_while_it_lowers(key, x).block_until_ready()
    (ev,) = [e for e in spans
             if "draws_while_it_lowers" in e["attributes"]["fun"]]
    assert 0.2 <= ev["attributes"]["trace_s"] < 0.4


def test_a_jax_without_time_spans_falls_back_to_durations(spans, monkeypatch):
    """Durations only: a start is then the moment the duration arrives
    less the duration."""
    import time

    monkeypatch.setattr(compile_cache, "_hears_time_spans", False)
    time.sleep(0.03)
    compile_cache._on_duration(compile_cache.TRACE, 0.03, fun_name="f")
    time.sleep(0.05)
    compile_cache._on_duration(compile_cache.LOWER, 0.02, fun_name="jit(f)")
    compile_cache._on_duration(compile_cache.BACKEND_COMPILE, 0.0,
                               fun_name="jit(f)")
    (ev,) = spans
    assert ev["attributes"]["trace_s"] == pytest.approx(0.03)
    assert ev["attributes"]["lower_s"] == pytest.approx(0.02)
    assert ev["attributes"]["lead_s"] == pytest.approx(0.08, abs=0.02)
