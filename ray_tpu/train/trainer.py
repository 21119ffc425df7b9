"""JaxTrainer: the TPU-native DataParallelTrainer.

Counterpart of the reference's DataParallelTrainer/TorchTrainer path
(reference: train/data_parallel_trainer.py:26 — training_loop :427;
torch/torch_trainer.py:11; fit entry base_trainer.py:651), redesigned as a
standalone Train-v2-style controller (reference:
train/v2/_internal/execution/controller/controller.py:91) so training does
not route through Tune (SURVEY.md §7 build-order note).

The per-worker loop runs JAX: on one worker per host, in-jit collectives
(psum under shard_map / pjit shardings) carry gradients over ICI; the
host-level collective group carries control-plane sync. With
``topology="mesh"`` a single worker drives every local chip as a Mesh —
the idiomatic single-controller SPMD mode.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import time
import uuid
from typing import Any, Callable

import ray_tpu
from ray_tpu._private.worker_context import global_runtime
from ray_tpu.exceptions import RayTpuError
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import (
    CheckpointConfig,
    FailureConfig,
    Result,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.train.worker_group import RunStateActor, WorkerGroup
from ray_tpu.util import state, tracing

logger = logging.getLogger(__name__)

TIMELINE_FILE = "timeline.json"


def _write_timeline(path: str) -> None:
    """The cluster's timeline as it stands, this process's own spans
    included, written where the run keeps its results. A run's outcome
    never depends on it."""
    try:
        global_runtime().report_rpc_now()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        state.timeline(path)
    except Exception:
        logger.warning("could not write %s", path, exc_info=True)


class JaxTrainer:
    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: dict | None = None,
        scaling_config: ScalingConfig | None = None,
        run_config: RunConfig | None = None,
        backend_config=None,
        datasets: dict[str, Any] | None = None,
    ):
        from ray_tpu.train.backend import JaxConfig

        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.backend_config = backend_config if backend_config is not None else JaxConfig()
        self.datasets = datasets or {}

    # ------------------------------------------------------------------

    def _dataset_shards(self, n: int) -> list[dict[str, Any]] | None:
        """Split datasets across workers (reference analogue: DataConfig +
        streaming_split, train/_internal/data_config.py:12)."""
        if not self.datasets:
            return None
        shards: list[dict[str, Any]] = [dict() for _ in range(n)]
        for name, ds in self.datasets.items():
            if hasattr(ds, "streaming_split"):
                for i, shard in enumerate(ds.streaming_split(n)):
                    shards[i][name] = shard
            elif hasattr(ds, "split"):
                for i, shard in enumerate(ds.split(n)):
                    shards[i][name] = shard
            else:
                for i in range(n):
                    shards[i][name] = ds
        return shards

    @staticmethod
    def _max_placeable_workers(scaling: ScalingConfig) -> int:
        """How many worker gangs the cluster can place right now, judged
        against TOTAL per-node capacity of alive nodes (reference:
        train/v2 scaling policy reacting to resource availability)."""
        per_worker = scaling.worker_resources()
        if not any(v > 0 for v in per_worker.values()):
            return scaling.num_workers  # zero-demand workers always fit
        fit = 0
        try:
            for node in ray_tpu.nodes():
                if not node.get("alive", True):
                    continue
                total = dict(node.get("resources", {}))
                while all(total.get(k, 0.0) >= v for k, v in per_worker.items()):
                    for k, v in per_worker.items():
                        total[k] = total.get(k, 0.0) - v
                    fit += 1
        except Exception:
            return scaling.num_workers
        return fit

    def fit(self) -> Result:
        """Run the job. Whatever its outcome, the run leaves its timeline
        at ``<Result.path>/timeline.json``: the spans of every process on
        the head's clock, as ``ray-tpu timeline`` draws them (open it in
        Perfetto)."""
        ray_tpu.api.auto_init()
        scaling = self.scaling_config
        if scaling.topology == "mesh" and scaling.num_workers != 1:
            raise ValueError("topology='mesh' uses a single controller worker")
        name = self.run_config.name or f"JaxTrainer_{uuid.uuid4().hex[:6]}"
        storage = self.run_config.resolved_storage_path()
        timeline_path = os.path.join(storage, TIMELINE_FILE)
        # A file found after a run is that run's.
        with contextlib.suppress(FileNotFoundError):
            os.remove(timeline_path)
        try:
            with tracing.span("train.fit", run=name,
                              workers=scaling.num_workers,
                              chips_per_worker=scaling.worker_resources()
                              .get("TPU", 0)):
                return self._fit(scaling, name, storage)
        finally:
            _write_timeline(timeline_path)

    def _fit(self, scaling: ScalingConfig, name: str, storage: str) -> Result:
        failure_config = self.run_config.failure_config or FailureConfig()
        ckpt_config = self.run_config.checkpoint_config or CheckpointConfig()

        state = RunStateActor.remote(storage, ckpt_config)
        state.set_run_info.remote(name, scaling.num_workers)
        failures_left = failure_config.max_failures
        latest_ckpt: str | None = None
        start_iteration = 0
        error: Exception | None = None

        while True:
            # Attempt-unique group name: collective groups and the torch
            # process-group rendezvous key (train/torch) are keyed by it —
            # a retry must never read the previous (dead) attempt's
            # rendezvous state.
            group = WorkerGroup(
                scaling, self.backend_config,
                group_name=f"train-{name}-{uuid.uuid4().hex[:8]}",
            )
            try:
                refs = group.run(
                    self.train_loop_per_worker,
                    self.train_loop_config,
                    state,
                    name,
                    latest_ckpt,
                    self._dataset_shards(scaling.num_workers),
                    start_iteration,
                )
                ray_tpu.get(refs)
                error = None
                break
            except RayTpuError as e:  # covers actor death, crashes, task errors
                error = e
                latest_ckpt = ray_tpu.get(state.latest_checkpoint_path.remote())
                start_iteration = len(ray_tpu.get(state.get_history.remote()))
                if failures_left == 0:
                    break
                if failures_left > 0:
                    failures_left -= 1
                if scaling.elastic:
                    # Elastic restart (reference: train/v2 scaling_policy +
                    # failure_handling): re-fit the gang to what the
                    # cluster can actually place now, down to min_workers.
                    # The next attempt recompiles at the new world size.
                    fit = self._max_placeable_workers(scaling)
                    new_n = max(scaling.min_workers, min(scaling.num_workers, fit))
                    if new_n != scaling.num_workers:
                        scaling = dataclasses.replace(scaling, num_workers=new_n)
                time.sleep(0.5)  # let worker-death cleanup settle
            finally:
                group.shutdown()

        state.finish_run.remote("ERRORED" if error is not None else
                                "FINISHED",
                                repr(error) if error is not None else None)
        history = ray_tpu.get(state.get_history.remote())
        best = ray_tpu.get(state.best_checkpoint_path.remote())
        result = Result(
            metrics=history[-1] if history else {},
            checkpoint=Checkpoint(best) if best else None,
            path=storage,
            metrics_history=history,
            error=error,
        )
        if error is not None:
            raise error
        return result
