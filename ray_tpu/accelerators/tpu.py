"""TPU accelerator manager: chip discovery, visibility pinning, pod gangs.

Counterpart of the reference's TPUAcceleratorManager
(reference: python/ray/_private/accelerators/tpu.py:109 — resource name
"TPU" :113, chip discovery via TPU_VISIBLE_CHIPS/GCE metadata :63-107,136,
visibility pinning :193 setting TPU_VISIBLE_CHIPS + TPU_CHIPS_PER_HOST_BOUNDS
:39-44, pod type detection :236, and the ``TPU-{pod_type}-head`` gang
resource advertised on worker 0 :375,419-434 so one task can claim a whole
pod slice).

Differences from the reference: no GCE metadata server calls (works from env
vars + device files, so it behaves identically in CI and on TPU VMs), and a
``tpu_pod_mesh`` helper that turns a claimed slice into a
``jax.sharding.Mesh`` — the reference stops at scheduling; here the mesh IS
the point (SURVEY.md §7).
"""

from __future__ import annotations

import glob
import os

NUM_TPUS_PER_HOST_DEFAULT = 4  # v4/v5e hosts expose 4 chips (8 for v5e-8 donut)

# Generations accepted in pod type strings, mirroring the reference's
# TPU_VALID_CHIP_OPTIONS (+v6e).
VALID_GENERATIONS = ("v2", "v3", "v4", "v5p", "v5litepod", "v5e", "v6e")


class TPUAcceleratorManager:
    """Static methods mirroring the reference AcceleratorManager ABC
    (reference: _private/accelerators/accelerator.py:5)."""

    # --- identity ---

    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return "TPU_VISIBLE_CHIPS"

    # --- discovery ---

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Number of TPU chips this process may hand out.

        Order: explicit TPU_VISIBLE_CHIPS; TPU_CHIP_COUNT (set by TPU VM
        images); the host's device files.
        """
        visible = os.environ.get("TPU_VISIBLE_CHIPS")
        if visible:
            return len([c for c in visible.split(",") if c != ""])
        count = os.environ.get("TPU_CHIP_COUNT")
        if count:
            try:
                return int(count)
            except ValueError:
                pass
        return host_chip_count()

    @staticmethod
    def get_current_node_tpu_pod_type() -> str | None:
        """Pod/slice type like ``v5litepod-8`` (reference :236)."""
        accel_type = os.environ.get("TPU_ACCELERATOR_TYPE")
        if accel_type and TPUAcceleratorManager.is_valid_tpu_accelerator_type(accel_type):
            return accel_type
        return None

    @staticmethod
    def is_valid_tpu_accelerator_type(accel_type: str) -> bool:
        """``{gen}-{cores}`` with a known generation (reference :60)."""
        parts = accel_type.split("-")
        if len(parts) != 2:
            return False
        gen, cores = parts
        return gen in VALID_GENERATIONS and cores.isdigit()

    @staticmethod
    def get_current_node_tpu_worker_id() -> int | None:
        """This host's index within the pod slice (reference :295)."""
        for var in ("TPU_WORKER_ID", "CLOUD_TPU_TASK_ID"):
            v = os.environ.get(var)
            if v is not None:
                try:
                    return int(v)
                except ValueError:
                    pass
        return None

    @staticmethod
    def get_num_workers_in_current_tpu_pod() -> int | None:
        """Host count of the pod slice (reference :312): chips / chips-per-host."""
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if pod_type is None:
            return None
        gen, cores = pod_type.split("-")
        n_cores = int(cores)
        # v2/v3/v5p pod types count cores (2 per chip); v4 counts... also
        # cores; v5litepod/v6e count chips directly.
        chips = n_cores if gen in ("v5litepod", "v5e", "v6e") else n_cores // 2
        per_host = TPUAcceleratorManager.get_current_node_num_accelerators() or NUM_TPUS_PER_HOST_DEFAULT
        return max(1, chips // per_host)

    # --- visibility pinning (reference :193) ---

    @staticmethod
    def set_current_process_visible_accelerator_ids(ids: list[str] | list[int]) -> None:
        """Confine this process's libtpu to ``ids``. Only effective
        before the process's first jax backend init."""
        for k, v in chip_process_env(ids, host_chip_count()).items():
            os.environ[k] = v

    # --- gang resources (reference :375,419-434) ---

    @staticmethod
    def get_current_node_additional_resources() -> dict[str, float]:
        """On pod-slice worker 0, advertise ``TPU-{pod_type}-head: 1`` so a
        single task/actor can claim the whole slice and then drive it as one
        mesh (docstring example at reference :397-404)."""
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        worker_id = TPUAcceleratorManager.get_current_node_tpu_worker_id()
        if pod_type is not None and worker_id == 0:
            return {f"TPU-{pod_type}-head": 1.0}
        return {}


def host_chip_nodes() -> list[str]:
    """The device files of the chips physically attached to this host:
    /dev/accel* (v2-v4 PCI) or the numbered /dev/vfio groups (v5e+; a
    one-chip v5e VM shows /dev/vfio/1, a four-chip host /dev/vfio/0-3 —
    the names are IOMMU groups, not chip ids), in number order. libtpu
    opens them exclusively and keeps them until its process is gone."""
    accel = glob.glob("/dev/accel*")
    if accel:
        return sorted(accel)
    return sorted((p for p in glob.glob("/dev/vfio/*")
                   if os.path.basename(p).isdigit()),
                  key=lambda p: int(os.path.basename(p)))


def host_chip_count() -> int:
    """Chips physically attached to this host, from its device files.
    Ignores TPU_VISIBLE_CHIPS: this is what a process would hold if
    nothing narrowed it."""
    return len(host_chip_nodes())


# libtpu's bounds ("x,y,z") for a process that owns a SUBSET of its
# host's chips, by subset size. 1: run on a v5e 2x2 host, four such
# processes at once, with nothing else set (no per-process port; the
# inherited TPU_CHIPS_PER_HOST_BOUNDS=2,2,1 / TPU_HOST_BOUNDS stay).
# Other sizes have no entry: chips are still narrowed by
# TPU_VISIBLE_CHIPS and libtpu raises at init if it cannot form them.
_SUBSET_BOUNDS = {1: "1,1,1"}


def chip_process_env(chips, host_chips: int) -> dict[str, str]:
    """The environment that makes a process hold exactly ``chips``
    (chip indexes on this host) — the one place both the actor and the
    task path of a chip-holding worker get it from. A process given
    every chip of the host needs no bounds at all; so does one on a
    host with no device files (``host_chips`` 0: a scheduling-only
    ``num_tpus`` with no libtpu behind it)."""
    env = {"TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips)}
    bounds = _SUBSET_BOUNDS.get(len(chips))
    if bounds and len(chips) < host_chips:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


# --- public helpers (reference analogue: python/ray/util/accelerators/tpu.py) ---


def pod_head_resource(pod_type: str) -> str:
    """Resource name claiming a whole pod slice, e.g. ``TPU-v5litepod-8-head``."""
    return f"TPU-{pod_type}-head"


def get_current_pod_name() -> str | None:
    """The TPU pod/slice name this host belongs to, if any."""
    return os.environ.get("TPU_NAME") or None


def get_current_pod_worker_count() -> int | None:
    return TPUAcceleratorManager.get_num_workers_in_current_tpu_pod()


def tpu_pod_mesh(axis_names=("data", "model"), shape=None):
    """Build a ``jax.sharding.Mesh`` over all addressable TPU devices.

    The bridge from the scheduling layer (a claimed slice) to the compute
    layer: tasks that hold the ``TPU-...-head`` gang resource call this to
    get the mesh their pjit/shard_map programs run on.
    """
    import numpy as np

    import jax

    devices = jax.devices()
    n = len(devices)
    if shape is None:
        shape = (n,) + (1,) * (len(axis_names) - 1)
    arr = np.array(devices).reshape(shape)
    return jax.sharding.Mesh(arr, axis_names)
