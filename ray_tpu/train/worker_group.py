"""Worker group: the gang of training worker actors.

Counterpart of the reference's WorkerGroup + BackendExecutor
(reference: train/_internal/worker_group.py:102; backend_executor.py:73 —
start :146, start_training :460). Workers are gang-scheduled through a
placement group built from ScalingConfig (reference: BackendExecutor builds
its PG from ScalingConfig the same way).
"""

from __future__ import annotations

import contextlib
import inspect
from typing import Any, Callable

import ray_tpu
from ray_tpu._private.worker_context import global_runtime, held_chips
from ray_tpu.train.backend import JaxConfig
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager
from ray_tpu.train.config import CheckpointConfig, ScalingConfig
from ray_tpu.util import tracing
from ray_tpu.util.placement_group import PlacementGroup, placement_group, remove_placement_group


@ray_tpu.remote(num_cpus=0)
class RunStateActor:
    """Collects worker reports; owns checkpoint registration.

    Reference analogue: the result-queue + checkpoint handling the trial
    actor does in train v1 (session.py:405 queue path) folded into one
    state actor (train v2 controller state).
    """

    def __init__(self, storage_path: str, ckpt_cfg: CheckpointConfig | None):
        ckpt_cfg = ckpt_cfg or CheckpointConfig()
        self.manager = CheckpointManager(
            storage_path,
            num_to_keep=ckpt_cfg.num_to_keep,
            score_attribute=ckpt_cfg.checkpoint_score_attribute,
            score_order=ckpt_cfg.checkpoint_score_order,
        )
        self.history: list[dict] = []
        self.storage_path = storage_path
        self._run_info: dict | None = None

    def set_run_info(self, name: str, num_workers: int) -> bool:
        """Register this run in the cluster KV so the dashboard's Train
        page can list live/finished runs (reference:
        dashboard/modules/train — run registry fed by the controller)."""
        import time as _time

        self._run_info = {
            "name": name, "status": "RUNNING",
            "num_workers": num_workers, "storage": self.storage_path,
            "started_at": _time.time(), "iterations": 0,
            "last_metrics": {},
        }
        self._publish()
        return True

    def finish_run(self, status: str, error: "str | None" = None) -> bool:
        if self._run_info is not None:
            self._run_info["status"] = status
            if error:
                self._run_info["error"] = error
            self._publish()
        return True

    def _publish(self) -> None:
        import json as _json

        if self._run_info is None:
            return
        info = dict(self._run_info,
                    iterations=len(self.history),
                    last_metrics=self.history[-1] if self.history else {},
                    best_checkpoint=self.best_checkpoint_path())
        try:
            from ray_tpu._private.worker_context import global_runtime

            global_runtime().kv_put(
                info["name"], _json.dumps(info, default=str).encode(),
                ns="__train__")
        except Exception:
            pass  # registry is best-effort observability

    def report(self, rank: int, iteration: int, metrics: dict, ckpt_staging_path: str | None):
        if ckpt_staging_path is not None:
            self.manager.register(ckpt_staging_path, metrics)
        if rank == 0:
            self.history.append(dict(metrics, training_iteration=iteration))
            self._publish()
        return True

    def get_history(self) -> list[dict]:
        return self.history

    def latest_checkpoint_path(self) -> str | None:
        c = self.manager.latest
        return c.path if c else None

    def best_checkpoint_path(self) -> str | None:
        c = self.manager.best
        return c.path if c else None


@ray_tpu.remote
class TrainWorker:
    """One training worker process (reference: the actors WorkerGroup
    spawns; execution path backend_executor.py:460 start_training)."""

    def __init__(self, rank: int, world_size: int, group_name: str, backend_config=None):
        self.rank = rank
        self.world_size = world_size
        self.group_name = group_name
        self._jax_backend = isinstance(backend_config, JaxConfig)
        if backend_config is not None:
            backend = backend_config.backend_cls()()
            # Dispatch on arity, not exception type: a TypeError raised
            # INSIDE setup must propagate, not trigger a silent re-run.
            params = inspect.signature(backend.on_worker_setup).parameters
            with tracing.span("train.worker.setup", rank=rank):
                if len(params) >= 4:
                    backend.on_worker_setup(rank, world_size, group_name, backend_config)
                else:
                    backend.on_worker_setup(rank, world_size, group_name)

    def run(
        self,
        fn: Callable,
        config: dict | None,
        collector,
        experiment_name: str,
        latest_ckpt_path: str | None,
        dataset_shards: dict[str, Any] | None,
        start_iteration: int = 0,
    ):
        from ray_tpu.train import session as session_mod

        session = session_mod.TrainSession(
            rank=self.rank,
            world_size=self.world_size,
            local_rank=self.rank,
            collector=collector,
            experiment_name=experiment_name,
            group_name=self.group_name,
            latest_checkpoint=Checkpoint(latest_ckpt_path) if latest_ckpt_path else None,
            dataset_shards=dataset_shards,
            start_iteration=start_iteration,
        )
        session_mod.set_session(session)
        try:
            with tracing.span("train.loop", rank=self.rank):
                if self._jax_backend and held_chips():
                    # What the loop's first ``jax.devices()`` would do,
                    # under a name: the import and libtpu opening the
                    # leased chips. A worker without chips starts nothing.
                    with tracing.span("train.backend_init") as attrs:
                        import jax

                        devices = jax.devices()
                        attrs.update(platform=devices[0].platform,
                                     device_kind=devices[0].device_kind,
                                     devices=len(devices))
                sig = inspect.signature(fn)
                if len(sig.parameters) == 0:
                    fn()
                else:
                    fn(config or {})
        finally:
            session_mod.set_session(None)
            # fit() kills this process right after the loop returns: put
            # its buffered spans on their way now (a cast, nothing awaited;
            # a report that cannot go out is not the loop's failure).
            with contextlib.suppress(Exception):
                global_runtime().report_rpc_now()
        return self.rank


class WorkerGroup:
    def __init__(
        self,
        scaling_config: ScalingConfig,
        backend_config,
        group_name: str,
    ):
        self.scaling_config = scaling_config
        self.group_name = group_name
        n = scaling_config.num_workers
        res = scaling_config.worker_resources()
        self.pg: PlacementGroup | None = None
        if n > 1:
            # Fail fast if the gang can never fit (reference analogue:
            # BackendExecutor's resource validation before PG wait).
            total = ray_tpu.cluster_resources()
            for k, v in res.items():
                if total.get(k, 0.0) < v * n:
                    raise ray_tpu.exceptions.PlacementGroupUnschedulableError(
                        f"ScalingConfig needs {v * n} {k} "
                        f"({n} workers x {v}), cluster has {total.get(k, 0.0)}"
                    )
            self.pg = placement_group([dict(res)] * n, strategy=scaling_config.placement_strategy)
            if not self.pg.wait(120):
                remove_placement_group(self.pg)
                raise ray_tpu.exceptions.PlacementGroupUnschedulableError(
                    f"placement group for {n} training workers not ready after 120s"
                )
        self.workers = []
        for rank in range(n):
            opts: dict = {
                "resources": {k: v for k, v in res.items() if k != "CPU"},
                "num_cpus": res.get("CPU", 1),
            }
            if self.pg is not None:
                opts["scheduling_strategy"] = ray_tpu.PlacementGroupSchedulingStrategy(
                    placement_group=self.pg, placement_group_bundle_index=rank
                )
            self.workers.append(
                TrainWorker.options(**opts).remote(rank, n, group_name, backend_config)
            )

    def run(self, fn, config, collector, experiment_name, latest_ckpt, shards_per_worker, start_iteration=0):
        return [
            w.run.remote(
                fn,
                config,
                collector,
                experiment_name,
                latest_ckpt,
                shards_per_worker[i] if shards_per_worker else None,
                start_iteration,
            )
            for i, w in enumerate(self.workers)
        ]

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
