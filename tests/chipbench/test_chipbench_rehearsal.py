"""Rehearsal compiles for the described ``v5e:2x2`` topology: the
configurations' train steps at real widths and the depths their files
freeze, asserting that each fits a chip's memory.

Nothing runs and nothing here is a speed. This is where "the largest
depth that fits" was settled before any chip time. The topology is
described inside a module-scoped fixture (never at import), and every
test of it lives in this one file, as the on-chip-measurement guide asks.
"""

from __future__ import annotations

import os
from unittest import mock

import pytest

HBM = 15.75 * 2**30     # what the v5e compiler allows a program (bytes)
SPARE = 1e9             # the step must leave this much


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-device compile is written to the persistent cache but
    # cannot be read back without a chip; keep these compiles out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _train_step(config_file: str, traffic_file: str, devices):
    """The cell's train step exactly as ``drivers/train_job.py`` jits it,
    lowered for ``devices``."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from chipbench import spec
    from ray_tpu import models
    from ray_tpu.parallel import (MeshConfig, batch_sharding,
                                  infer_param_specs, make_shardings)

    data = spec.load_json("chipbench", "configs", config_file)
    t = spec.load_json("chipbench", "traffic", traffic_file)
    cfg = spec.model_config(data)
    mesh = MeshConfig(data=1, fsdp=-1).build(devices)
    o = data["optimizer"]
    opt = optax.adamw(o["learning_rate"], weight_decay=o["weight_decay"])
    shapes = cfg.shapes()
    shardings = make_shardings(mesh, infer_param_specs(
        shapes, mesh, models.partition_specs(cfg)))
    replicated = NamedSharding(mesh, PartitionSpec())
    params = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, shardings)
    from chipbench.drivers.train_job import moment_shardings

    state = {"params": params,
             "opt_state": jax.tree.map(
                 lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                    sharding=sh),
                 jax.eval_shape(opt.init, shapes),
                 moment_shardings(opt, shapes, shardings, replicated)),
             "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)}
    state_shardings = jax.tree.map(lambda x: x.sharding, state)
    rows = t["rows_per_chip"] * len(devices)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (rows, t["seq_len"] + 1), jnp.int32, sharding=batch_sharding(mesh))}
    step = jax.jit(models.make_train_step(cfg, opt, mesh=mesh),
                   donate_argnums=(0,), out_shardings=(state_shardings, None))
    # ``attention(impl="auto")`` asks ``jax.devices()[0].platform``, which
    # here is the CPU: steer it, in the test, to the described chips, so
    # that it takes the branch it takes on the chip (the Pallas kernel).
    with mock.patch.object(jax, "devices", lambda *a, **k: list(devices)):
        return step.lower(state, batch), cfg


def test_gpt2_xl_step_fits_one_chip_with_a_gigabyte_spare(topo):
    lowered, cfg = _train_step("gpt2-xl-1chip.json", "pretrain-8x1024.json",
                               [topo.devices[0]])
    assert cfg.n_layers % 4 == 0
    used = _bytes(lowered.compile())
    print(f"gpt2-xl-1chip {cfg.n_layers} layers: {used / 1e9:.2f} GB")
    assert used <= HBM - SPARE, used
    assert "tpu_custom_call" not in lowered.as_text()   # T=1024: no kernel


def test_mistral_fsdp4_step_fits_the_2x2_host(topo):
    lowered, cfg = _train_step("mistral-7b-fsdp4.json", "finetune-4x1024.json",
                               list(topo.devices))
    compiled = lowered.compile()
    used = _bytes(compiled)           # per device
    print(f"mistral-7b-fsdp4 {cfg.n_layers} layers: {used / 1e9:.2f} GB a chip")
    assert used <= HBM - SPARE, used
    assert "tpu_custom_call" not in lowered.as_text()   # T=1024: no kernel
    text = compiled.as_text()
    assert "all-gather" in text and "reduce-scatter" in text
