"""What a subprocess's environment says to jax.

libtpu gives a host's chips to the first process that initializes it,
so which children may reach for them is decided where they are spawned:
``JAX_PLATFORMS=cpu`` keeps a child off the chips.

Multi-chip behavior is validated on a virtual N-device CPU mesh (the way
the reference simulates multi-node clusters in-process — SURVEY.md §4,
reference: python/ray/cluster_utils.py:135); the XLA flag sets the
device count.
"""

from __future__ import annotations

import os

from ray_tpu._private import compile_cache


def worker_jax_env(tpu_capable: bool) -> dict:
    """What the head and the node agents add to every worker's spawn
    environment: the one compile-cache directory, and for chipless
    workers the CPU pin. A ``tpu_capable`` worker keeps the spawner's
    own platform setting and is pointed at its leased chips before its
    first jax use (Worker._hold_chips)."""
    env = {compile_cache.ENV_VAR: compile_cache.compile_cache_dir()}
    if not tpu_capable:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def hermetic_cpu_env(n_devices: int,
                     base: "dict[str, str] | None" = None) -> dict:
    """Environment for a subprocess that must run jax on ``n_devices``
    virtual CPU devices whatever accelerator this host has."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env
