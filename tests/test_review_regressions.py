"""Regression tests for defects found in review: wait() cap, actor FIFO with
unresolved deps, failed-creation resource release, re-creation block reuse.
"""

import time

import pytest

import ray_tpu
from ray_tpu.exceptions import ActorDiedError, TaskError


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_wait_caps_at_num_returns(cluster):
    refs = [ray_tpu.put(i) for i in range(5)]
    time.sleep(0.1)
    ready, not_ready = ray_tpu.wait(refs, num_returns=2, timeout=5)
    assert len(ready) == 2 and len(not_ready) == 3


def test_actor_call_order_with_pending_dep(cluster):
    @ray_tpu.remote
    def slow_value():
        time.sleep(0.8)
        return "set"

    @ray_tpu.remote
    class State:
        def __init__(self):
            self.v = "unset"

        def set(self, v):
            self.v = v

        def read(self):
            return self.v

    s = State.remote()
    s.set.remote(slow_value.remote())  # dep not ready yet
    # Submitted after set: must NOT overtake it.
    assert ray_tpu.get(s.read.remote(), timeout=20) == "set"


def test_failed_actor_creation_releases_resources(cluster):
    @ray_tpu.remote(num_cpus=3)
    class Broken:
        def __init__(self):
            raise RuntimeError("bad init")

        def ping(self):
            return 1

    b = Broken.remote()
    with pytest.raises((ActorDiedError, TaskError)):
        ray_tpu.get(b.ping.remote(), timeout=20)
    # The 3-CPU reservation must come back; a subsequent 4-CPU task must run.
    @ray_tpu.remote(num_cpus=4)
    def needs_all():
        return "ran"

    deadline = time.time() + 15
    while time.time() < deadline:
        if ray_tpu.available_resources().get("CPU", 0) >= 4:
            break
        time.sleep(0.2)
    assert ray_tpu.get(needs_all.remote(), timeout=20) == "ran"


def test_store_no_leak_on_recreate(cluster):
    import numpy as np

    from ray_tpu._private.worker_context import get_head

    head = get_head()
    base = head.arena.in_use
    rt = __import__("ray_tpu._private.worker_context", fromlist=["global_runtime"]).global_runtime()
    # Write the same object id twice (simulates a retry rewriting a return).
    ref = rt.put(np.ones(200_000), _object_id="deadbeef" * 4)
    rt.put(np.ones(200_000), _object_id="deadbeef" * 4)
    used = head.arena.in_use - base
    assert used <= 200_000 * 8 + 65536, f"leaked block: {used}"
    rt.free([ref], force=True)


def test_tpu_accelerator_manager_env(monkeypatch):
    from ray_tpu.accelerators.tpu import TPUAcceleratorManager as M

    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")
    assert M.get_current_node_num_accelerators() == 4
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    monkeypatch.setenv("TPU_CHIP_COUNT", "8")
    assert M.get_current_node_num_accelerators() == 8
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-8")
    assert M.get_current_node_tpu_pod_type() == "v5litepod-8"
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    assert M.get_current_node_additional_resources() == {"TPU-v5litepod-8-head": 1.0}
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    assert M.get_current_node_additional_resources() == {}
    assert M.is_valid_tpu_accelerator_type("v4-16")
    assert not M.is_valid_tpu_accelerator_type("h100-8")
    M.set_current_process_visible_accelerator_ids([0, 1, 2, 3])
    import os

    # The bounds that go with a chip set are chip_process_env's
    # (tests/test_chip_placement.py).
    assert os.environ["TPU_VISIBLE_CHIPS"] == "0,1,2,3"


def test_process_runtime_env_refcounted():
    """ADVICE r1: a finished task's env must not linger as the
    process-level fallback; concurrent tasks see last-started-wins and
    the actor-lifetime base env returns once all are done."""
    from ray_tpu._private import worker_context as wc

    base = {"env_vars": {"A": "base"}}
    wc.set_process_base_runtime_env(base)
    try:
        assert wc.get_process_runtime_env() == base
        t1 = wc.push_process_runtime_env({"env_vars": {"A": "t1"}})
        t2 = wc.push_process_runtime_env({"env_vars": {"A": "t2"}})
        assert wc.get_process_runtime_env() == {"env_vars": {"A": "t2"}}
        wc.pop_process_runtime_env(t2)
        assert wc.get_process_runtime_env() == {"env_vars": {"A": "t1"}}
        wc.pop_process_runtime_env(t1)
        # No stale per-call env after the last task finishes.
        assert wc.get_process_runtime_env() == base
        wc.pop_process_runtime_env(t1)  # double-pop is harmless
        assert wc.get_process_runtime_env() == base
    finally:
        wc.set_process_base_runtime_env(None)


def test_pipelined_nested_get_no_deadlock():
    """Same-shape pipelining (r4 control-plane) parks child tasks on a
    busy worker's queue; a parent task blocking on its OWN nested child
    must hand the queue to an overflow drainer instead of deadlocking
    (Worker._on_will_block). Depth-3 nesting exercises the recursive
    hand-off."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1, object_store_memory=32 * 1024 * 1024)
    try:
        @ray_tpu.remote
        def leaf(x):
            return x + 1

        @ray_tpu.remote
        def mid(x):
            return ray_tpu.get(leaf.remote(x)) + 10

        @ray_tpu.remote
        def top(x):
            return ray_tpu.get(mid.remote(x)) + 100

        # One CPU => one pool worker: every nested child is pipelined
        # onto the same (blocked) worker.
        assert ray_tpu.get(top.remote(1), timeout=60) == 112
        assert ray_tpu.get(
            [top.remote(i) for i in range(8)], timeout=60) == [
            111 + i for i in range(8)]
    finally:
        ray_tpu.shutdown()


def test_datasink_setup_failure_routes_through_on_write_failed():
    """Datasink lifecycle (reference: data/datasource/datasink.py): a
    failure in on_write_start is a WRITE failure — it must invoke
    on_write_failed with the exception before re-raising, exactly like
    a failure in write() (regression: on_write_start used to run
    outside the try, skipping the failure hook)."""
    from ray_tpu.data import from_items
    from ray_tpu.data.dataset import Datasink

    events: list = []

    class FailsAtSetup(Datasink):
        def on_write_start(self):
            events.append("start")
            raise RuntimeError("staging setup failed")

        def write(self, block):
            events.append("write")

        def on_write_complete(self):
            events.append("complete")

        def on_write_failed(self, error):
            events.append(("failed", str(error)))

    ds = from_items([{"x": 1}, {"x": 2}])
    with pytest.raises(RuntimeError, match="staging setup failed"):
        ds.write_datasink(FailsAtSetup())
    assert events == ["start", ("failed", "staging setup failed")]
