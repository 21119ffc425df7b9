"""Chip placement.

A fake ``num_tpus`` stands in for the chips: nothing here touches
libtpu, so what is checked is the runtime's half — every request that
fits is placed within seconds, concurrent holders see disjoint chip
ids, a holder's chips come back only when its process is gone, chipless
workers are pinned to the CPU, and a request that can never fit raises
instead of waiting forever.
"""

from __future__ import annotations

import os
import time

import pytest

import ray_tpu
from ray_tpu.accelerators.tpu import chip_process_env
from ray_tpu.exceptions import TaskUnschedulableError

ENV_KEYS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
            "TPU_PROCESS_BOUNDS", "JAX_PLATFORMS")


def _env():
    return {k: os.environ.get(k) for k in ENV_KEYS} | {"pid": os.getpid()}


@ray_tpu.remote
def env_task():
    return _env()


@ray_tpu.remote
class EnvActor:
    def env(self):
        return _env()


def _chips(env: dict) -> list[int]:
    return [int(c) for c in env["TPU_VISIBLE_CHIPS"].split(",")]


@pytest.fixture
def cluster(request):
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=request.param,
                 object_store_memory=32 * 1024 * 1024)
    yield request.param
    ray_tpu.shutdown()


def _wait_free(chips: int, timeout: float = 20.0) -> None:
    deadline = time.monotonic() + timeout
    while ray_tpu.available_resources().get("TPU", 0) < chips:
        assert time.monotonic() < deadline, ray_tpu.available_resources()
        time.sleep(0.05)


@pytest.mark.parametrize("cluster", [1, 2, 4], indirect=True)
def test_every_size_places_as_task_and_as_actor(cluster):
    chips = cluster
    from ray_tpu._private.worker_context import get_head

    assert type(get_head()).__name__ == "Head"  # one head process
    for k in range(1, chips + 1):
        env = ray_tpu.get(env_task.options(num_tpus=k).remote(), timeout=30)
        assert len(_chips(env)) == k, env
        actor = EnvActor.options(num_tpus=k).remote()
        env = ray_tpu.get(actor.env.remote(), timeout=30)
        assert len(_chips(env)) == k, env
        ray_tpu.kill(actor)
        _wait_free(chips)


@pytest.mark.parametrize("cluster", [4], indirect=True)
def test_concurrent_holders_see_disjoint_chips(cluster):
    actors = [EnvActor.options(num_tpus=1).remote() for _ in range(4)]
    envs = ray_tpu.get([a.env.remote() for a in actors], timeout=30)
    assert sorted(c for e in envs for c in _chips(e)) == [0, 1, 2, 3]
    assert len({e["pid"] for e in envs}) == 4
    assert ray_tpu.available_resources().get("TPU", 0) == 0
    # Two go; a two-chip holder gets an aligned pair, not whatever is
    # left over first.
    for a in (actors[1], actors[2]):
        ray_tpu.kill(a)
    _wait_free(2)
    ray_tpu.kill(actors[0])
    pair = EnvActor.options(num_tpus=2).remote()
    assert _chips(ray_tpu.get(pair.env.remote(), timeout=30)) == [0, 1]


@pytest.mark.parametrize("cluster", [2], indirect=True)
def test_a_chip_lease_is_for_the_life_of_the_process(cluster):
    """A finished chip task's worker is retired, never re-pointed, and
    its chips return only once the process is gone."""
    first = ray_tpu.get(env_task.options(num_tpus=2).remote(), timeout=30)
    second = ray_tpu.get(env_task.options(num_tpus=2).remote(), timeout=30)
    assert first["pid"] != second["pid"]
    _wait_free(2)
    for pid in (first["pid"], second["pid"]):
        with pytest.raises(OSError):
            os.kill(pid, 0)


@pytest.mark.parametrize("cluster", [1], indirect=True)
def test_chipless_workers_are_pinned_to_the_cpu(cluster, monkeypatch):
    env = ray_tpu.get(env_task.remote(), timeout=30)
    assert env["JAX_PLATFORMS"] == "cpu" and env["TPU_VISIBLE_CHIPS"] is None
    actor = EnvActor.remote()
    assert ray_tpu.get(actor.env.remote(), timeout=30)["JAX_PLATFORMS"] == "cpu"


@pytest.mark.parametrize("cluster", [2], indirect=True)
def test_impossible_requests_raise(cluster):
    with pytest.raises(TaskUnschedulableError, match="no node has more"):
        ray_tpu.get(env_task.options(num_tpus=3).remote(), timeout=30)
    with pytest.raises(TaskUnschedulableError, match="no node has more"):
        EnvActor.options(num_tpus=3).remote()
    with pytest.raises(TaskUnschedulableError, match="whole chips"):
        ray_tpu.get(env_task.options(num_tpus=0.5).remote(), timeout=30)
    # ...and leave the cluster usable.
    assert ray_tpu.get(env_task.options(num_tpus=2).remote(), timeout=30)


def test_chips_on_a_joined_node_place():
    """A node agent's chips get a pool of their own (they had none: work
    placed there waited forever)."""
    from tests.test_multinode import _start_agent, _wait_nodes
    from ray_tpu._private.worker_context import get_head

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=0,
                 object_store_memory=32 * 1024 * 1024)
    head = get_head()
    agent = _start_agent(f"{head.address[0]}:{head.address[1]}",
                         resources='{"TPU": 2}', node_id="node-tpu")
    # Defined here so it pickles by value: the agent's workers do not
    # have this test module on their path.
    @ray_tpu.remote(num_tpus=2)
    class Holder:
        def chips(self):
            import os

            return os.environ.get("TPU_VISIBLE_CHIPS")

    try:
        _wait_nodes(2)
        assert ray_tpu.get(Holder.remote().chips.remote(),
                           timeout=60) == "0,1"
    finally:
        agent.kill()
        agent.wait(timeout=10)
        ray_tpu.shutdown()


def test_chip_process_env():
    """The one function both worker paths take their environment from."""
    one = chip_process_env([2], host_chips=4)
    assert one == {"TPU_VISIBLE_CHIPS": "2",
                   "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                   "TPU_PROCESS_BOUNDS": "1,1,1"}
    # Every chip of the host, or a host with no device files (a fake
    # num_tpus): no bounds at all.
    assert chip_process_env([0, 1, 2, 3], host_chips=4) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3"}
    assert chip_process_env([0], host_chips=1) == {"TPU_VISIBLE_CHIPS": "0"}
    assert chip_process_env([1], host_chips=0) == {"TPU_VISIBLE_CHIPS": "1"}
