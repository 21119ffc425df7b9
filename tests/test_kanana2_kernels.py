"""What latent attention (kanana-2, PR 35) brought to
``ops/attention.py``: values narrower than queries and keys, and a part
of the key that ALL heads share (the rotary key), in the three Pallas
kernels (interpret mode) against ``dot_product_attention`` on the joined
192 / 128-shaped operands, in forward and every gradient, with and
without a window; every other attention path on the same operands; the
kernels an equal-width caller gets (the parent's arithmetic of tiles and
VMEM, written out here, and the ``pallas_call``s' operands); and the kept
``out`` / ``lse`` under the layer's checkpoint, as
``tests/test_flash_remat.py`` holds them for plain attention. A file of
its own beside ``tests/test_kanana2.py`` so that the two run on two
workers. CPU only, float32."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import pytest

from ray_tpu import models

attention = importlib.import_module("ray_tpu.ops.attention")

B, H = 2, 3
NOPE, ROPE, WIDE = 32, 16, 24      # q / k without positions, rotary, value


def _operands(t, seed=0):
    """(q_nope, k_nope, v, q_rope [B, T, H, ROPE], k_rope [B, T, ROPE],
    the output's cotangent)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((B, t, H, NOPE), (B, t, H, NOPE), (B, t, H, WIDE),
              (B, t, H, ROPE), (B, t, ROPE), (B, t, H, WIDE))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def _joined(q, k, v, q_rope, k_rope):
    """The 192 / 128-shaped operands a kernel with ONE key width takes:
    the rotary key repeated to every head."""
    repeated = jnp.broadcast_to(k_rope[:, :, None], q_rope.shape)
    return (jnp.concatenate([q, q_rope], -1),
            jnp.concatenate([k, repeated], -1), v)


def _reference(window):
    def fn(q, k, v, q_rope, k_rope):
        return attention.dot_product_attention(
            *_joined(q, k, v, q_rope, k_rope), causal=True, window=window)
    return fn


CASES = [(512, 128, 128, None), (512, 256, 128, None), (512, 128, 128, 200),
         (1024, 128, 256, 129), (256, 256, 256, None)]


@pytest.mark.parametrize("t,block_q,block_k,window", CASES)
def test_shared_key_kernels_equal_the_reference(t, block_q, block_k, window):
    """Scores ``q_nope k_nope^T + q_rope k_rope^T`` over sqrt(32 + 16),
    values 24 wide: forward, and dq, dk, dv, dq_rope and dk_rope (the sum
    over the heads of what each head's kernel wrote)."""
    *ops, g = _operands(t)
    want = _reference(window)(*ops)
    got = attention.flash_attention(*ops[:3], True, block_q, block_k, window,
                                    *ops[3:])
    assert got.shape == (B, t, H, WIDE)
    assert float(jnp.abs(got - want).max()) < 5e-6
    every = (0, 1, 2, 3, 4)
    want_g = jax.grad(lambda *a: (_reference(window)(*a) * g).sum(),
                      every)(*ops)
    got_g = jax.grad(lambda *a: (attention.flash_attention(
        *a[:3], True, block_q, block_k, window, *a[3:]) * g).sum(),
        every)(*ops)
    for name, a, b in zip(("dq", "dk", "dv", "dq_rope", "dk_rope"), got_g,
                          want_g):
        assert a.shape == b.shape, name
        assert float(jnp.abs(b).max()) > 0.1, name
        assert float(jnp.abs(a - b).max()) < 3e-5, name


@pytest.mark.parametrize("t,block_q,block_k,window", CASES[:4])
def test_kernels_take_a_value_width_of_their_own(t, block_q, block_k, window):
    """The 192-wide form: ONE key of 48 columns a head, values of 24, no
    shared part. v is not padded: ``dv`` and the output are 24 wide."""
    *ops, g = _operands(t, seed=1)
    q, k, v = _joined(*ops)
    assert (q.shape[-1], k.shape[-1], v.shape[-1]) == (48, 48, 24)
    want = attention.dot_product_attention(q, k, v, causal=True, window=window)
    got = attention.flash_attention(q, k, v, True, block_q, block_k, window)
    assert float(jnp.abs(got - want).max()) < 5e-6
    want_g = jax.grad(lambda *a: (attention.dot_product_attention(
        *a, causal=True, window=window) * g).sum(), (0, 1, 2))(q, k, v)
    got_g = jax.grad(lambda *a: (attention.flash_attention(
        *a, True, block_q, block_k, window) * g).sum(), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got_g, want_g):
        assert a.shape == b.shape and float(jnp.abs(a - b).max()) < 3e-5, name


@pytest.mark.parametrize("impl", ["reference", "blockwise", "auto", "flash"])
@pytest.mark.parametrize("t,window", [(512, None), (512, 100), (1280, None)])
def test_every_attention_path_takes_the_shared_key(impl, t, window):
    """``attention(q_shared=, k_shared=)``: the kernel takes the parts
    apart; every other path gets them joined, the rotary key repeated."""
    *ops, _ = _operands(t, seed=2)
    want = _reference(window)(*ops)
    got = attention.attention(*ops[:3], impl=impl, window=window,
                              q_shared=ops[3], k_shared=ops[4])
    assert float(jnp.abs(got - want).max()) < 5e-6


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _kernel_calls(fn, *args):
    return [e for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


def test_the_rotary_key_is_not_repeated_to_the_heads():
    """The forward kernel's fifth operand is the rotary key as the layer
    made it, [B, T, ROPE]: one a token, which every head's grid row reads
    through its index map; and no operand is padded: v stays 24 wide."""
    *ops, g = _operands(512)
    (call,) = _kernel_calls(lambda *a: attention.flash_attention(
        *a[:3], True, 128, 128, None, *a[3:]), *ops)
    shapes = [v.aval.shape for v in call.invars]
    assert shapes == [(B * H, 512, NOPE), (B * H, 512, NOPE),
                      (B * H, 512, WIDE), (B * H, 512, ROPE), (B, 512, ROPE)]
    assert [v.aval.shape for v in call.outvars] == [(B * H, 512, WIDE),
                                                    (B * H, 512, 1)]
    calls = _kernel_calls(jax.grad(lambda *a: (attention.flash_attention(
        *a[:3], True, 128, 128, None, *a[3:]) * g).sum(), (0, 1, 2, 3, 4)),
        *ops)
    assert len(calls) == 2          # forward; the one backward kernel
    for call in calls:
        assert (B, 512, ROPE) in [v.aval.shape for v in call.invars]
        assert all(v.aval.shape[0] in (B, B * H) for v in call.invars)
    # every head writes its own gradient of the rotary key; the sum over
    # the heads is outside the kernel. dk, dv, dk_shared, then dq and
    # dq_shared, which the same kernel accumulates since PR 38
    assert [v.aval.shape for v in calls[1].outvars] == [
        (B * H, 512, NOPE), (B * H, 512, WIDE), (B * H, 512, ROPE),
        (B * H, 512, NOPE), (B * H, 512, ROPE)]


# -- an equal-width caller gets the kernels it got ----------------------------------

def _parent_vmem_bytes(kernel, block_q, block_k, d, dtype):
    """``_flash_vmem_bytes`` as the parent commit (4334b8c) had it: ONE
    width ``d`` for q, k and v."""
    tile = attention._vmem_tile
    io = jnp.dtype(dtype).itemsize
    q_rows, k_rows = tile(block_q, d, io), tile(block_k, d, io)
    column = tile(block_q, 1, 4)
    if kernel == "fwd":
        blocks = 2 * q_rows + 2 * k_rows + column
        scratch = 2 * column + tile(block_q, d, 4)
    elif kernel == "dq":
        blocks = 3 * q_rows + 2 * k_rows + 2 * column
        scratch = tile(block_q, d, 4)
    else:       # "bwd" (PR 38): "dkv" and dq's output block; the head's
        # float32 dq is counted by its rows, ``tq``, which these calls leave 0
        blocks = (3 if kernel == "bwd" else 2) * q_rows + 4 * k_rows \
            + 2 * column
        scratch = 2 * tile(block_k, d, 4)
    scores = attention._FLASH_SCORE_TILES[kernel] * tile(block_q, block_k, 4)
    return 2 * blocks + scratch + scores


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv", "bwd"])
@pytest.mark.parametrize("d", [64, 128, 512])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_equal_widths_size_their_tiles_as_the_parent_did(kernel, d, dtype):
    for bq in (128, 512, 1024):
        for bk in (128, 1024):
            assert attention._flash_vmem_bytes(kernel, bq, bk, d, dtype) == \
                attention._flash_vmem_bytes(kernel, bq, bk, d, dtype, d, 0) == \
                _parent_vmem_bytes(kernel, bq, bk, d, dtype)
    for t in (1280, 4096, 16384):
        assert attention._flash_tiles(kernel, t, t, d, dtype) == \
            attention._flash_tiles(kernel, t, t, d, dtype, d, 0)
    # a narrower value or a shared part changes the account, never upward
    # of the widest case the parent knew
    assert attention._flash_vmem_bytes(kernel, 512, 512, 256, dtype, 128) < \
        _parent_vmem_bytes(kernel, 512, 512, 256, dtype)
    assert attention._flash_vmem_bytes(kernel, 512, 512, 128, dtype, 128, 64) \
        > _parent_vmem_bytes(kernel, 512, 512, 128, dtype)


def test_equal_widths_launch_the_three_operand_kernels():
    """q, k, v of one width and no shared part: the forward kernel has
    three operands and the backward's six, as before; the results are the
    reference's."""
    q, k, v, *_ = _operands(512, seed=3)
    v = k + 1.0
    calls = _kernel_calls(jax.grad(lambda *a: attention.flash_attention(
        *a, True, 128, 128, None).sum(), (0, 1, 2)), q, k, v)
    assert [len(c.invars) for c in calls] == [3, 6]
    assert [len(c.outvars) for c in calls] == [2, 3]
    want = attention.dot_product_attention(q, k, v, causal=True)
    got = attention.flash_attention(q, k, v, True, 128, 128, None)
    assert float(jnp.abs(got - want).max()) < 5e-6


def test_the_cells_tiles_at_8192():
    """The benchmark cell's shape, bfloat16: 1024-row tiles in the
    forward and in the two-kernel backward, for the shared-key form (128
    + 64 against 128) and for the 192-wide one; each within the rule's
    VMEM budget. The one backward kernel keeps the head's dq beside its
    tiles (both parts: 8 MiB): 1024 x 1024 is 33.0 MiB by the rule's
    arithmetic, so it steps to (512, 1024), which ran as fast on the v5e
    (``_FLASH_VMEM_MOST``'s comment). And how a plain causal kernel walks
    those tiles."""
    for d, dv, dr in ((128, 128, 64), (192, 128, 0)):
        for kernel in ("fwd", "dq", "dkv"):
            assert attention._flash_tiles(kernel, 8192, 8192, d, jnp.bfloat16,
                                          dv, dr) == (1024, 1024)
            assert attention._flash_vmem_bytes(
                kernel, 1024, 1024, d, jnp.bfloat16, dv, dr) \
                <= attention._FLASH_VMEM_MOST
        assert attention._flash_tiles("bwd", 8192, 8192, d, jnp.bfloat16, dv,
                                      dr) == (512, 1024)
        assert attention._flash_vmem_bytes(
            "bwd", 512, 1024, d, jnp.bfloat16, dv, dr, 8192) \
            <= attention._FLASH_VMEM_MOST < attention._flash_vmem_bytes(
            "bwd", 1024, 1024, d, jnp.bfloat16, dv, dr, 8192)
    # The walk at those tiles (PR 50; counted tile by tile in
    # ``tests/test_smallthinker_kernels.py``): the forward 8 steps a query
    # block, the backward 16 a key block, which start at the first block
    # that sees the row block and stay on the last one once past it.
    steps, k_of = attention._flash_inner(True, None, 1024, 1024, 8, 8, True)
    assert steps == 8
    assert [int(k_of(2, s)) for s in range(8)] == [0, 1, 2, 2, 2, 2, 2, 2]
    steps, q_of = attention._flash_inner(True, None, 1024, 512, 8, 16, False)
    assert steps == 16
    assert [int(q_of(5, s)) for s in range(16)] == [*range(10, 16)] + 10 * [15]


# -- the kept output and logsumexp under the layer's checkpoint ----------------------

T = 256


def _mla(**kw):
    return models.kanana_2_30b_a3b(
        n_layers=3, d_model=64, n_heads=4, d_ff=32, kv_latent=32,
        d_head_nope=16, d_head_rope=8, d_head_v=16, d_ff_dense=96,
        d_ff_shared=48, n_experts=8, expert_top_k=3, vocab_size=256,
        max_seq_len=T, dtype="float32", attn_impl="flash", **kw)


def _inputs(cfg, seed=0):
    params = models.init_params(jax.random.PRNGKey(seed), cfg)
    for stack in ("layers", "dense_layers"):
        params[stack] = jax.tree.map(lambda a: a * 5.0, params[stack])
    rows = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, T + 1), 0,
                              cfg.vocab_size)
    return params, rows


def _loss(cfg):
    return lambda params, rows: models.lm_loss(params, {"tokens": rows},
                                               cfg)[0]


@pytest.fixture
def unpoliced(monkeypatch):
    """Inside: ``forward`` checkpoints its layers with no name policy."""
    checkpoint = jax.checkpoint
    policies = jax.checkpoint_policies
    dots = policies.dots_with_no_batch_dims_saveable

    def without_names(fun, *, policy=None, **kw):
        return checkpoint(fun, policy=policy if policy is dots else None,
                          **kw)

    monkeypatch.setattr(jax, "checkpoint", without_names)
    monkeypatch.setattr(policies, "save_from_both_policies",
                        lambda first, names: first)


@pytest.mark.parametrize("remat_policy", ["full", "dots"])
def test_latent_layers_backward_runs_two_kernels_a_layer(remat_policy,
                                                         request):
    """Two scan bodies in the jaxpr (the dense stack's and the expert
    layers'; ``unroll`` is the scan's parameter, its body is there once),
    one layer each: two ``pallas_call``s a body (three until PR 38 made
    the backward one kernel) where a checkpoint with no name policy has
    three; the same gradients, bit for bit."""
    cfg = _mla(remat_policy=remat_policy)
    params, rows = _inputs(cfg)
    grad = jax.value_and_grad(_loss(cfg))
    assert len(_kernel_calls(grad, params, rows)) == 2 * 2
    loss, grads = grad(params, rows)
    request.getfixturevalue("unpoliced")
    grad = jax.value_and_grad(_loss(cfg))       # traced anew, unpoliced
    assert len(_kernel_calls(grad, params, rows)) == 3 * 2
    want_loss, want = grad(params, rows)
    assert float(loss) == float(want_loss)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        assert bool(jnp.array_equal(a, b)), jax.tree_util.keystr(path)
