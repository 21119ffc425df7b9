"""Mutable shm channels + channel-compiled DAGs.

Reference: core_worker/experimental_mutable_object_manager.h:44
(WriteAcquire/ReadAcquire/ReadRelease), experimental/channel/
shared_memory_channel.py, dag/compiled_dag_node.py:806 (pinned actor
loops over reusable channels)."""

from __future__ import annotations

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.dag.nodes import InputNode, MultiOutputNode
from ray_tpu.experimental.channel import (
    Channel,
    ChannelClosed,
    ChannelTimeout,
)


@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_channel_roundtrip_and_backpressure():
    # num_slots=1: single-slot mutable-object semantics, where the
    # second write must wait for the release of the first.
    ch = Channel(capacity=1 << 20, num_readers=1, num_slots=1)
    rd = Channel(name=ch.name, _create=False)
    ch.write({"x": np.arange(8), "tag": "m"})
    v = rd.begin_read()
    assert v["tag"] == "m" and v["x"].sum() == 28
    rd.end_read()

    # Second write must wait for release.
    ch.write(1)
    assert rd.begin_read() == 1
    with pytest.raises(ChannelTimeout):
        ch.write(2, timeout_s=0.2)
    rd.end_read()
    ch.write(2)
    assert rd.read() == 2

    ch.close()
    with pytest.raises(ChannelClosed):
        rd.begin_read(timeout_s=1.0)


def test_channel_capacity_enforced():
    ch = Channel(capacity=1024, num_readers=1)
    with pytest.raises(ValueError, match="exceeds channel capacity"):
        ch.write(np.zeros(100000))


def test_channel_ring_runahead():
    """num_slots=4 lets the writer run 4 messages ahead before blocking;
    the reader then drains them in order."""
    ch = Channel(capacity=1 << 16, num_readers=1, num_slots=4)
    rd = Channel(name=ch.name, _create=False)
    for i in range(4):
        ch.write(i, timeout_s=2)  # none consumed yet — must not block
    with pytest.raises(ChannelTimeout):
        ch.write(99, timeout_s=0.2)  # ring full
    assert [rd.read() for _ in range(4)] == [0, 1, 2, 3]
    ch.write(4)
    assert rd.read() == 4


def test_channel_survives_creator_gc():
    """The shm region must outlive the CREATOR handle: the last attached
    handle unlinks, not the creating one (old bug: __del__ on the
    creator unlinked while a reader still drained the ring)."""
    import gc

    ch = Channel(capacity=1 << 16, num_readers=1, num_slots=4)
    name = ch.name
    rd = Channel(name=name, _create=False)
    for i in range(3):
        ch.write(i)
    del ch
    gc.collect()
    # Reader still drains the messages AND the region is still openable.
    assert [rd.read() for _ in range(3)] == [0, 1, 2]
    rd2 = Channel(name=name, _create=False)
    del rd2
    shm_path = "/dev/shm" + name
    import os as _os
    assert _os.path.exists(shm_path)
    del rd
    gc.collect()
    assert not _os.path.exists(shm_path)  # last detacher unlinked


def test_channel_write_abort_on_serialization_failure(monkeypatch):
    """A failure AFTER write_acquire (serializing into the mapped slot)
    must abort the acquired slot — otherwise every later write_acquire
    returns NULL and is misreported as ChannelTimeout forever."""
    from ray_tpu._private import serialization

    ch = Channel(capacity=1 << 16, num_readers=1, num_slots=1)
    rd = Channel(name=ch.name, _create=False)

    real_write_to = serialization.write_to
    calls = {"n": 0}

    def failing_write_to(view, header, buffers):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom mid-slot")
        return real_write_to(view, header, buffers)

    monkeypatch.setattr(serialization, "write_to", failing_write_to)
    with pytest.raises(RuntimeError, match="boom mid-slot"):
        ch.write("doomed")
    # Pre-acquire failures (plain unpicklable value) must not wedge
    # either — serialize() raises before the slot is touched.
    class Bomb:
        def __reduce__(self):
            raise RuntimeError("boom early")

    with pytest.raises(Exception, match="boom early"):
        ch.write(Bomb())
    ch.write("after")  # would raise ChannelTimeout if the slot leaked
    assert rd.read() == "after"


def test_channel_cross_process(cluster):
    """A channel pickled to an actor moves data without the object
    store per message."""

    @ray_tpu.remote
    class Consumer:
        def consume(self, chan, n):
            rd = chan
            total = 0.0
            for _ in range(n):
                v = rd.begin_read(timeout_s=30)
                total += float(v.sum())
                rd.end_read()
            return total

    ch = Channel(capacity=1 << 20, num_readers=1)
    c = Consumer.remote()
    ref = c.consume.remote(ch, 5)
    for i in range(5):
        ch.write(np.full(100, float(i)))
    assert ray_tpu.get(ref, timeout=30) == sum(i * 100 for i in range(5))


def test_compiled_dag_channel_pipeline(cluster):
    """2-stage actor pipeline compiles to channel mode; results flow
    per-execution with no task submission."""

    @ray_tpu.remote
    class Stage:
        def __init__(self, k):
            self.k = k

        def apply(self, x):
            return x + self.k

    a = Stage.remote(1)
    b = Stage.remote(10)
    with InputNode() as inp:
        dag = b.apply.bind(a.apply.bind(inp))
    compiled = dag.experimental_compile()
    try:
        assert compiled._mode == "channels"
        for i in range(20):
            assert compiled.execute(i).get(timeout_s=30) == i + 11
    finally:
        compiled.teardown()


def test_compiled_dag_fanout_multi_output(cluster):
    @ray_tpu.remote
    class S:
        def __init__(self, k):
            self.k = k

        def f(self, x):
            return x * self.k

    a, b = S.remote(2), S.remote(3)
    with InputNode() as inp:
        dag = MultiOutputNode([a.f.bind(inp), b.f.bind(inp)])
    compiled = dag.experimental_compile()
    try:
        assert compiled._mode == "channels"
        assert compiled.execute(5).get(timeout_s=30) == [10, 15]
        assert compiled.execute(7).get(timeout_s=30) == [14, 21]
    finally:
        compiled.teardown()


def test_compiled_dag_error_propagates_and_dag_survives(cluster):
    @ray_tpu.remote
    class S:
        def f(self, x):
            if x < 0:
                raise ValueError("negative input")
            return x + 1

    a = S.remote()
    with InputNode() as inp:
        dag = a.f.bind(inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled._mode == "channels"
        assert compiled.execute(1).get(timeout_s=30) == 2
        with pytest.raises(Exception, match="negative input"):
            compiled.execute(-1).get(timeout_s=30)
        # The pipeline stays usable after a per-execution error.
        assert compiled.execute(5).get(timeout_s=30) == 6
    finally:
        compiled.teardown()


def test_compiled_dag_same_actor_local_memo(cluster):
    """Two steps on one actor pass values in-process, not via channels."""

    @ray_tpu.remote
    class S:
        def first(self, x):
            return x + 1

        def second(self, x):
            return x * 2

    a = S.remote()
    with InputNode() as inp:
        dag = a.second.bind(a.first.bind(inp))
    compiled = dag.experimental_compile()
    try:
        assert compiled._mode == "channels"
        # first's output has no cross-actor consumer: only the final
        # output channel exists (plus input + ready).
        data_chans = [n for n in compiled._channels if "ready" not in n]
        assert len(data_chans) == 2  # input + output
        assert compiled.execute(4).get(timeout_s=30) == 10
    finally:
        compiled.teardown()


def test_compiled_dag_revisited_actor(cluster):
    """A -> B -> A: the revisited actor must run its early step (feeding
    B) before blocking on B's output — lazy per-step channel acquisition,
    not read-everything-up-front."""

    @ray_tpu.remote
    class S:
        def __init__(self, tag):
            self.tag = tag

        def f(self, x):
            return x + [self.tag]

    a, b = S.remote("a"), S.remote("b")
    with InputNode() as inp:
        dag = a.f.bind(b.f.bind(a.f.bind(inp)))
    compiled = dag.experimental_compile()
    try:
        assert compiled._mode == "channels"
        assert compiled.execute([]).get(timeout_s=30) == ["a", "b", "a"]
        assert compiled.execute(["x"]).get(timeout_s=30) == \
            ["x", "a", "b", "a"]
    finally:
        compiled.teardown()


def test_compiled_dag_multi_output_error_keeps_stream_aligned(cluster):
    """One branch failing must still drain BOTH output channels, so the
    next execution's outputs pair correctly."""

    @ray_tpu.remote
    class S:
        def __init__(self, fail_on):
            self.fail_on = fail_on

        def f(self, x):
            if x == self.fail_on:
                raise ValueError(f"boom on {x}")
            return x * 10

    a, b = S.remote(2), S.remote(None)
    with InputNode() as inp:
        dag = MultiOutputNode([a.f.bind(inp), b.f.bind(inp)])
    compiled = dag.experimental_compile()
    try:
        assert compiled._mode == "channels"
        assert compiled.execute(1).get(timeout_s=30) == [10, 10]
        r_bad = compiled.execute(2)
        r_good = compiled.execute(3)
        with pytest.raises(Exception, match="boom on 2"):
            r_bad.get(timeout_s=30)
        # A failed ref keeps raising the same error on repeat get.
        with pytest.raises(Exception, match="boom on 2"):
            r_bad.get(timeout_s=30)
        assert r_good.get(timeout_s=30) == [30, 30]
    finally:
        compiled.teardown()


def test_compiled_dag_out_of_order_get_fails_loudly(cluster):
    @ray_tpu.remote
    class S:
        def f(self, x):
            return x

    a = S.remote()
    with InputNode() as inp:
        dag = a.f.bind(inp)
    compiled = dag.experimental_compile()
    try:
        r1 = compiled.execute(1)
        r2 = compiled.execute(2)
        with pytest.raises(RuntimeError, match="submission order"):
            r2.get(timeout_s=30)
        assert r1.get(timeout_s=30) == 1
        assert r2.get(timeout_s=30) == 2
    finally:
        compiled.teardown()


def test_compiled_dag_const_only_source_falls_back(cluster):
    """An actor step with no per-execution input would free-run; such
    graphs use the legacy path."""

    @ray_tpu.remote
    class S:
        def f(self):
            return 7

    a = S.remote()
    dag = a.f.bind()
    compiled = dag.experimental_compile()
    try:
        assert compiled._mode == "legacy"
        assert ray_tpu.get(compiled.execute(), timeout=30) == 7
    finally:
        compiled.teardown()


def test_compiled_dag_function_node_falls_back(cluster):
    @ray_tpu.remote
    def plain(x):
        return x - 1

    with InputNode() as inp:
        dag = plain.bind(inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled._mode == "legacy"
        # ensure_compiled turns the silent fallback into an error users
        # can opt into (the fast path was NOT taken here).
        with pytest.raises(RuntimeError, match="fell back"):
            compiled.ensure_compiled()
        ref = compiled.execute(10)
        assert ray_tpu.get(ref, timeout=30) == 9
    finally:
        compiled.teardown()


def test_compiled_dag_throughput_vs_actor_calls(cluster):
    """The channel pipeline beats by-ref actor calls on 1 MiB payloads.
    CI floor is 2x: this test also runs on single-core boxes where every
    hop is a context switch; on multi-core hosts the spin-path puts the
    gap at an order of magnitude."""

    @ray_tpu.remote
    class Fwd:
        def f(self, x):
            return x

    a = Fwd.remote()
    payload = np.random.rand(128, 1024)  # 1 MiB

    # Baseline: by-ref actor calls through the object store.
    ref = ray_tpu.put(payload)
    n_base = 50
    ray_tpu.get(a.f.remote(ref), timeout=30)
    t0 = time.time()
    for _ in range(n_base):
        ray_tpu.get(a.f.remote(ref), timeout=30)
    base_rate = n_base / (time.time() - t0)

    with InputNode() as inp:
        dag = a.f.bind(inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled._mode == "channels"
        compiled.execute(payload).get(timeout_s=30)  # warm
        n = 200
        window: list = []
        t0 = time.time()
        for _ in range(n):
            if len(window) >= 3:  # ring depth: keep the pipe full
                window.pop(0).get(timeout_s=30)
            window.append(compiled.execute(payload))
        for r in window:
            r.get(timeout_s=30)
        chan_rate = n / (time.time() - t0)
    finally:
        compiled.teardown()
    assert chan_rate > 2 * base_rate, (chan_rate, base_rate)
