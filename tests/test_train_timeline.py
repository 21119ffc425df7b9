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
