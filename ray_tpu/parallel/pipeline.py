"""SPMD pipeline parallelism: GPipe-style microbatch rotation in one
XLA program.

The reference builds pipelines as MPMD actor DAGs with NCCL p2p channels
(reference: dag/compiled_dag_node.py:806, experimental/channel/
torch_tensor_nccl_channel.py:44, execution schedule dag/
dag_node_operation.py). On TPU the idiomatic equivalent keeps the whole
pipeline inside a single jitted SPMD program: every device runs the same
``lax.scan`` loop over clock ticks; stage-to-stage transfer is a
``ppermute`` ring over the ``pipeline`` mesh axis, so XLA overlaps the
permute DMA with the next tick's compute — the role the reference's
mutable-plasma double buffers play
(core_worker/experimental_mutable_object_manager.h:44).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.parallel.mesh import AXIS_PIPELINE


def pipeline_stage_params(params_per_stage):
    """Stack per-stage param pytrees along a leading stage axis so each
    pipeline rank slices out its own stage (shard the leading axis over
    the pipeline mesh axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_per_stage)


def spmd_pipeline(stage_fn, stage_params, microbatches, *, axis_name=AXIS_PIPELINE):
    """Run ``stage_fn`` as a pipeline over the ``axis_name`` mesh axis.

    Call *inside* shard_map. Every rank holds ``stage_params`` for its own
    stage and the full stack of ``microbatches`` [n_micro, micro, ...]
    (stage 0's copy is the one that matters; dead inputs on other ranks
    are DCE'd by XLA where possible).

    Returns outputs [n_micro, micro, ...], valid on the *last* stage
    (other ranks hold zeros — combine with a ppermute/all_gather or let
    the loss live on the last stage).
    """
    stage = jax.lax.axis_index(axis_name)
    n_stages = jax.lax.axis_size(axis_name)
    n_micro = microbatches.shape[0]
    total_ticks = n_micro + n_stages - 1
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry
        # Stage 0 injects microbatch t (clamped once the bubble drains);
        # later stages consume what the previous tick permuted in.
        inject = microbatches[jnp.clip(t, 0, n_micro - 1)]
        x = jnp.where(stage == 0, inject, state)
        y = stage_fn(stage_params, x)
        # Microbatch index emerging from the last stage at tick t:
        out_idx = t - (n_stages - 1)
        write = (stage == n_stages - 1) & (out_idx >= 0)
        updated = outputs.at[jnp.clip(out_idx, 0, n_micro - 1)].set(y)
        outputs = jnp.where(write, updated, outputs)
        state = jax.lax.ppermute(y, axis_name, ring)
        return (state, outputs), None

    # The carry varies per pipeline rank; mark it so (shard_map VMA rule).
    state0 = jax.lax.pcast(jnp.zeros_like(microbatches[0]), (axis_name,), to="varying")
    outputs0 = jax.lax.pcast(jnp.zeros_like(microbatches), (axis_name,), to="varying")
    (_, outputs), _ = jax.lax.scan(
        tick, (state0, outputs0), jnp.arange(total_ticks)
    )
    return outputs


def pipeline_last_to_all(outputs, *, axis_name=AXIS_PIPELINE):
    """Broadcast last-stage pipeline outputs to every rank (for losses or
    metrics computed off-pipeline). One ring hop per stage."""
    n_stages = jax.lax.axis_size(axis_name)
    # all_gather then select the last stage's copy: simple and XLA lowers
    # it to an efficient ring on ICI.
    gathered = jax.lax.all_gather(outputs, axis_name)
    return gathered[n_stages - 1]


def pipelined_apply(stage_fn, params_per_stage, mesh, batch, *, num_microbatches):
    """Convenience jitted wrapper: split ``batch`` into microbatches, run
    the shard_map'd pipeline over ``mesh``'s pipeline axis, return the
    full output batch on all ranks."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import mesh_axis_size

    n_stages = mesh_axis_size(mesh, AXIS_PIPELINE)
    if n_stages == 1:
        # Degenerate mesh (pipeline axis collapsed): sequential apply.
        out = batch
        for p in params_per_stage:
            out = stage_fn(p, out)
        return out
    if len(params_per_stage) != n_stages:
        raise ValueError(
            f"{len(params_per_stage)} stages != pipeline axis size {n_stages}"
        )

    stacked = pipeline_stage_params(params_per_stage)
    micro = batch.reshape((num_microbatches, -1) + batch.shape[1:])

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(AXIS_PIPELINE), P()),
        out_specs=P(),
        # The all_gather-and-select in pipeline_last_to_all makes the
        # output replicated, but the static VMA check can't prove it.
        check_vma=False,
    )
    def run(stacked_params, microbatches):
        own = jax.tree.map(lambda p: p[0], stacked_params)
        outs = spmd_pipeline(stage_fn, own, microbatches)
        return pipeline_last_to_all(outs)

    out = run(stacked, micro)
    return out.reshape(batch.shape[:1] + out.shape[2:])
