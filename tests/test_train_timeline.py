"""A run leaves its timeline: ``JaxTrainer.fit`` writes the cluster's
chrome trace to ``<Result.path>/timeline.json`` whatever its outcome, with
the spans of the job's own processes in it: ``runtime.init`` and
``train.fit`` from the driver, ``train.loop`` and whatever the loop
recorded, its last line included, from the worker ``fit`` kills right
after."""

from __future__ import annotations

import json
import os

import pytest

import ray_tpu
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.train.trainer import TIMELINE_FILE


@pytest.fixture(scope="module")
def cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def _loop(config):
    from ray_tpu import train
    from ray_tpu.util import tracing

    with tracing.span("user.first", step=0):
        pass
    train.report({"loss": 1.0})
    if config.get("fail"):
        raise ValueError("the loop gives up")
    with tracing.span("user.last_line", step=1):
        pass


def _spans(path: str) -> dict[str, list[dict]]:
    with open(path) as f:
        events = json.load(f)
    out: dict[str, list[dict]] = {}
    for e in events:
        if e.get("cat") == "span":
            out.setdefault(e["name"], []).append(e)
    return out


def _inside(inner: dict, outer: dict) -> bool:
    return outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_fit_leaves_a_timeline_and_removes_a_stale_one(cluster, tmp_path):
    run = RunConfig(name="leaves-a-timeline", storage_path=str(tmp_path))
    path = os.path.join(run.resolved_storage_path(), TIMELINE_FILE)
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as f:
        f.write("stale: not even json")

    result = JaxTrainer(_loop, train_loop_config={},
                        scaling_config=ScalingConfig(num_workers=1),
                        run_config=run).fit()
    assert os.path.join(result.path, TIMELINE_FILE) == path
    spans = _spans(path)                         # json again: the run's own

    (fit,) = [e for e in spans["train.fit"]
              if e["args"].get("run") == "leaves-a-timeline"]
    assert fit["args"]["workers"] == 1
    (loop,) = [e for e in spans["train.loop"] if _inside(e, fit)]
    assert loop["tid"] != fit["tid"]             # another process
    # what the loop recorded, down to its last line before it returned
    for name in ("user.first", "train.report", "user.last_line"):
        (ev,) = [e for e in spans[name] if _inside(e, loop)]
        assert ev["tid"] == loop["tid"] and ev["args"]["parent"] == "train.loop"
    (setup,) = [e for e in spans["train.worker.setup"] if _inside(e, fit)]
    assert setup["tid"] == loop["tid"] and setup["args"]["rank"] == 0
    assert setup["ts"] + setup["dur"] <= loop["ts"]
    # runtime.init closed after init had made a runtime: it is kept
    init = spans["runtime.init"][-1]
    assert init["tid"] == fit["tid"] and init["args"]["head"] == "started"
    assert init["ts"] + init["dur"] <= fit["ts"]
    # a worker whose lease has no chips starts no backend and holds none
    assert "train.backend_init" not in spans
    assert "worker.hold_chips" not in spans


def test_a_failed_run_leaves_its_timeline_too(cluster, tmp_path):
    run = RunConfig(name="fails", storage_path=str(tmp_path))
    with pytest.raises(ray_tpu.exceptions.RayTpuError):
        JaxTrainer(_loop, train_loop_config={"fail": True},
                   scaling_config=ScalingConfig(num_workers=1),
                   run_config=run).fit()
    spans = _spans(os.path.join(run.resolved_storage_path(), TIMELINE_FILE))
    (fit,) = [e for e in spans["train.fit"]
              if e["args"].get("run") == "fails"]
    assert fit["args"]["failed"] is True and "error" in fit["args"]
    (loop,) = [e for e in spans["train.loop"] if _inside(e, fit)]
    assert loop["args"]["failed"] is True
    assert "the loop gives up" in loop["args"]["error"]
    assert not any(_inside(e, loop) for e in spans.get("user.last_line", []))


def test_a_timeline_that_cannot_be_written_costs_the_run_nothing(
        cluster, tmp_path, monkeypatch):
    from ray_tpu.train import trainer

    def refuse(path):
        raise OSError("disk full")

    monkeypatch.setattr(trainer.state, "timeline", refuse)
    result = JaxTrainer(_loop, train_loop_config={},
                        scaling_config=ScalingConfig(num_workers=1),
                        run_config=RunConfig(name="no-disk",
                                             storage_path=str(tmp_path))).fit()
    assert result.metrics["loss"] == 1.0
    assert not os.path.exists(os.path.join(result.path, TIMELINE_FILE))


@pytest.fixture
def emitted(monkeypatch):
    from ray_tpu.util import tracing

    events: list[dict] = []
    monkeypatch.setattr(tracing, "_emit", events.append)
    return events


@pytest.mark.parametrize("chips, backend, expected", [
    ([0], "jax", True),       # a chip lease: the backend starts under a name
    (None, "jax", False),     # no chips: nothing recorded, nothing imported
    ([0], "other", False),    # another framework's worker keeps its chips
])
def test_the_loop_opens_with_the_backends_start_only_on_a_chip_lease(
        chips, backend, expected, emitted, monkeypatch):
    from ray_tpu.train import backend as backend_mod
    from ray_tpu.train import worker_group

    monkeypatch.setattr(worker_group, "held_chips", lambda: chips)
    monkeypatch.setattr(backend_mod.JaxBackend, "on_worker_setup",
                        lambda self, *a, **k: None)
    config = (backend_mod.JaxConfig() if backend == "jax"
              else backend_mod.BackendConfig())
    worker = worker_group.TrainWorker._cls(0, 1, "unit", config)
    seen = []
    assert worker.run(lambda: seen.append(len(emitted)), None, None,
                      "unit", None, None) == 0
    by_name = {e["name"]: e for e in emitted}
    assert ("train.backend_init" in by_name) is expected
    loop = by_name["train.loop"]
    if expected:
        init = by_name["train.backend_init"]
        assert init["parent"] == "train.loop"
        assert loop["start"] <= init["start"] <= init["end"] <= loop["end"]
        assert init["attributes"]["platform"] == "cpu"
        assert init["attributes"]["devices"] >= 1
        assert init["attributes"]["device_kind"]
        assert "seconds" not in init["attributes"]
        # closed before the user's function ran
        assert seen == [emitted.index(init) + 1]


def test_a_lease_records_its_wait_once(emitted, monkeypatch):
    """``Worker._hold_chips`` on a first lease: one ``worker.hold_chips``
    span with what ``await_chips_free`` returned; the repeated push that
    every later task brings records nothing."""
    import types

    from ray_tpu._private import worker, worker_context, worker_exit
    from ray_tpu.accelerators import tpu

    probed, pointed = [], []

    def wait(nodes):
        probed.append(list(nodes))
        return 2.5

    monkeypatch.setattr(worker_exit, "await_chips_free", wait)
    monkeypatch.setattr(tpu, "host_chip_nodes",
                        lambda: ["/dev/vfio/0", "/dev/vfio/1"])
    monkeypatch.setattr(
        tpu.TPUAcceleratorManager,
        "set_current_process_visible_accelerator_ids",
        staticmethod(pointed.append))
    monkeypatch.setattr(worker_context, "_held_chips", None)
    me = types.SimpleNamespace(_chips=None, worker_id="w-unit")

    worker.Worker._hold_chips(me, [1])
    (ev,) = emitted
    assert ev["name"] == "worker.hold_chips"
    assert ev["attributes"] == {"chips": [1], "nodes": 1, "waited_s": 2.5}
    assert probed == [["/dev/vfio/1"]] and pointed == [[1]]
    assert me._chips == [1] and worker_context.held_chips() == [1]

    worker.Worker._hold_chips(me, [1])           # every push repeats it
    assert len(emitted) == 1 and len(probed) == 1
    with pytest.raises(RuntimeError, match="cannot be re-pointed"):
        worker.Worker._hold_chips(me, [0])
    assert len(emitted) == 1
