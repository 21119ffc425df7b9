"""Multi-slice (DCN) meshes on the virtual 8-device CPU mesh.

SURVEY.md §7 hard part (f): cross-slice scaling = a leading dcn mesh
axis carrying data parallelism, ICI axes inside each slice. These tests
simulate 2 slices x 4 devices and compile/execute a full hierarchical
train step, which is also what dryrun-style validation can exercise
without multi-slice hardware."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from ray_tpu.parallel.mesh import MeshConfig
from ray_tpu.parallel.multislice import (
    AXIS_DCN,
    build_multislice_mesh,
    dcn_allreduce_axes,
    detect_num_slices,
    multislice_batch_axes,
)


def test_detect_num_slices_cpu():
    assert detect_num_slices() == 1  # CPU devices expose no slice_index


def test_build_multislice_mesh_shapes():
    mesh = build_multislice_mesh(num_slices=2,
                                 per_slice=MeshConfig(fsdp=2, tensor=2))
    assert mesh.axis_names == (AXIS_DCN, "fsdp", "tensor")
    assert dict(mesh.shape) == {AXIS_DCN: 2, "fsdp": 2, "tensor": 2}
    assert multislice_batch_axes(mesh) == (AXIS_DCN, "fsdp")
    assert dcn_allreduce_axes(mesh) == (AXIS_DCN, "fsdp")

    with pytest.raises(ValueError, match="not divisible"):
        build_multislice_mesh(num_slices=3)


def test_psum_over_dcn_axis():
    """A psum naming the dcn axis compiles and reduces across slices."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = build_multislice_mesh(num_slices=2,
                                 per_slice=MeshConfig(data=4))
    x = jnp.arange(8.0).reshape(8, 1)
    xs = jax.device_put(
        x, NamedSharding(mesh, PartitionSpec((AXIS_DCN, "data"))))

    @jax.jit
    def total(v):
        return jax.shard_map(
            lambda s: jax.lax.psum(jnp.sum(s), (AXIS_DCN, "data")),
            mesh=mesh,
            in_specs=PartitionSpec((AXIS_DCN, "data")),
            out_specs=PartitionSpec(),
        )(v)

    assert float(total(xs)) == float(x.sum())


def test_hierarchical_train_step_2x4():
    """Full train step on a 2-slice mesh: dp across dcn, fsdp+tp inside
    each slice — gradients reduce over (dcn, fsdp), params shard over
    fsdp/tensor within a slice."""
    import optax

    from ray_tpu import models
    from ray_tpu.parallel.multislice import multislice_batch_axes
    from ray_tpu.parallel.sharding import infer_param_specs, make_shardings

    mesh = build_multislice_mesh(num_slices=2,
                                 per_slice=MeshConfig(fsdp=2, tensor=2))
    cfg = models.tiny(dtype="float32")
    opt = optax.sgd(1e-2)
    state = models.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    specs = infer_param_specs(state["params"], mesh,
                              models.partition_specs(cfg))
    shardings = make_shardings(mesh, specs)
    state["params"] = jax.tree.map(jax.device_put, state["params"],
                                   shardings)
    step = jax.jit(models.make_train_step(cfg, opt, mesh=mesh),
                   donate_argnums=(0,))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32),
                                          0, cfg.vocab_size)}
    from jax.sharding import NamedSharding, PartitionSpec

    batch = {
        "tokens": jax.device_put(
            batch["tokens"],
            NamedSharding(mesh,
                          PartitionSpec(multislice_batch_axes(mesh)))),
    }
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    state, metrics2 = step(state, batch)
    assert float(metrics2["loss"]) < float(metrics["loss"]) + 1.0
