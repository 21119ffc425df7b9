"""Training backends: per-worker distributed setup.

Counterpart of the reference's Backend ABC + TorchConfig
(reference: train/backend.py:32 Backend; train/torch/config.py:36 TorchConfig,
:66 _setup_torch_process_group, :115 dist.init_process_group(nccl|gloo)).

The JaxConfig backend replaces the NCCL/gloo process group with:
  - a host-level collective group (ray_tpu.util.collective) for control-plane
    sync (weight broadcast, metric reduction, barriers), and
  - on real multi-host TPU pods, ``jax.distributed.initialize`` so in-jit
    collectives span hosts over ICI/DCN — the data plane
    (SURVEY.md §2.4 row "Data parallel").
"""

from __future__ import annotations

import dataclasses
import os


@dataclasses.dataclass
class BackendConfig:
    def backend_cls(self):
        return Backend


class Backend:
    """Hooks called around the training lifecycle (reference train/backend.py:32)."""

    def on_start(self, worker_group, backend_config) -> None:
        pass

    def on_worker_setup(self, rank: int, world_size: int, group_name: str) -> None:
        pass

    def on_shutdown(self, worker_group, backend_config) -> None:
        pass


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    """JAX/TPU backend config.

    distributed="auto": initialize jax.distributed only when a multi-host
    environment is detected (TPU_WORKER_HOSTNAMES / coordinator env); "off"
    never; "on" always (requires coordinator_address).
    """

    distributed: str = "auto"
    coordinator_address: str | None = None
    collective_group: bool = True

    def backend_cls(self):
        return JaxBackend


class JaxBackend(Backend):
    def on_worker_setup(self, rank: int, world_size: int, group_name: str, config: JaxConfig | None = None) -> None:
        config = config or JaxConfig()
        # torchrun-style env vars for user code parity (reference:
        # train/torch/xla/config.py:41-56 sets the same family).
        os.environ["RANK"] = str(rank)
        os.environ["WORLD_SIZE"] = str(world_size)
        os.environ["LOCAL_RANK"] = str(rank)
        if config.collective_group and world_size > 1:
            from ray_tpu.util import collective

            collective.init_collective_group(world_size, rank, group_name=group_name)
        if config.distributed == "on" or (
            config.distributed == "auto" and self._is_multihost_pod()
        ):
            import jax

            coordinator = config.coordinator_address
            if coordinator is None:
                hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
                if hosts and hosts[0]:
                    coordinator = f"{hosts[0]}:8476"
            # A failure raises in "auto" too: a pod host that trains on
            # alone is a wrong run, not a degraded one.
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=int(os.environ.get("TPU_POD_PROCESS_COUNT", world_size)),
                process_id=rank,
            )

    @staticmethod
    def _is_multihost_pod() -> bool:
        hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h]
        return len(hosts) > 1
