"""Ring attention: sequence/context parallelism over the ICI ring.

Greenfield relative to the reference — repo-wide greps for ring
attention / Ulysses / sequence_parallel / context_parallel come up empty
there (SURVEY.md §2.4, §5 "Long-context"); its closest machinery is NCCL
p2p channels in compiled graphs. Here long context is first-class: the
sequence is sharded over a ``sequence`` mesh axis; each device computes
attention for its local query shard while key/value shards rotate around
the ring via ``ppermute``, folded in with the online softmax. Peak memory
per chip is O(T/n) and the ppermute DMA overlaps the current block's
matmuls (the permute is issued before the block compute that uses the
resident shard).

Call ``ring_attention`` inside shard_map with q/k/v already sharded on
the sequence axis; ``ring_attention_sharded`` wraps the shard_map.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _finalize, online_softmax_block, _NEG_INF
from ray_tpu.parallel.mesh import AXIS_SEQUENCE


def ring_attention(q, k, v, *, axis_name: str = AXIS_SEQUENCE,
                   causal: bool = True):
    """Attention over a sequence-sharded q/k/v inside shard_map.

    q, k, v: [B, T_local, H, D] — this rank's contiguous sequence shard
    (rank r holds global positions [r*T_local, (r+1)*T_local)).
    Returns [B, T_local, H, D].
    """
    rank = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    b, t_local, h, d = q.shape
    ring = [(i, (i + 1) % n) for i in range(n)]

    q_pos = rank * t_local + jnp.arange(t_local)

    def fold(k_cur, v_cur, m, l, o, s):
        # After s hops along the +1 ring, this rank holds the shard that
        # originated at rank - s.
        src = jax.lax.rem(rank - s + n, n)
        k_pos = src * t_local + jnp.arange(t_local)
        return online_softmax_block(
            q, k_cur, v_cur, m, l, o, q_pos=q_pos, k_pos=k_pos, causal=causal
        )

    def step(carry, s):
        k_cur, v_cur, m, l, o = carry
        # Issue this shard's permute before folding it in so the DMA
        # overlaps the block's matmuls (XLA schedules independent ops
        # together).
        k_nxt = jax.lax.ppermute(k_cur, axis_name, ring)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, ring)
        m, l, o = fold(k_cur, v_cur, m, l, o, s)
        return (k_nxt, v_nxt, m, l, o), None

    m0 = jnp.full((b, h, t_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    o0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    if n == 1:
        m, l, o = fold(k, v, m0, l0, o0, jnp.int32(0))
        return _finalize(o, l).astype(q.dtype)
    # n-1 permuted steps in the scan; the last resident shard is folded
    # outside the loop so no dead permute crosses the ring.
    (k_last, v_last, m, l, o), _ = jax.lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(n - 1)
    )
    m, l, o = fold(k_last, v_last, m, l, o, jnp.int32(n - 1))
    return _finalize(o, l).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, *, axis_name: str = AXIS_SEQUENCE,
                           causal: bool = True, batch_spec=None):
    """shard_map wrapper: q/k/v are global [B, T, H, D]; the sequence dim
    is sharded over ``axis_name``, batch over ``batch_spec`` axes."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import blockwise_attention
    from ray_tpu.parallel.mesh import mesh_axis_size

    if mesh_axis_size(mesh, axis_name) == 1:
        # Degenerate mesh (sequence axis collapsed): no ring needed.
        return blockwise_attention(q, k, v, causal=causal)

    spec = P(batch_spec, axis_name)

    fn = partial(ring_attention, axis_name=axis_name, causal=causal)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
