"""``ray_tpu/ops/state_space.py`` on the CPU: the chunked state-space scan
against the token-by-token recurrence, forward and backward, at a length
that is no whole number of chunks and with decays under which a naive
``exp(-G)`` overflows float32; the convolution chain with its bias and the
gated group norm against their plain forms, and the norm's Pallas kernels
(interpreted, two groups of 256 lanes) against the plain norm; and what
``ops/moe.py`` gained for experts WITHOUT a gate projection (``relu2``,
``_held_block`` and the dropless path on two grouped matmuls, the
lane-whole padding)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import linear_attention as la
from ray_tpu.ops import moe
from ray_tpu.ops import state_space as ss

B, T, H, P, G, S, CHUNK = 2, 45, 4, 8, 2, 16, 16


def _operands(seed: int, rates):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, T, H, P))
    b, c = (jax.random.normal(k[i], (B, T, G, S)) for i in (1, 2))
    dt = 3 * jax.nn.softplus(jax.random.normal(k[3], (B, T, H)))
    return x, dt, -dt * jnp.asarray(rates), b, c, jax.random.normal(k[4], (H,))


def _chunked(*ops):
    return ss.ssm_scan(*ops, chunk=CHUNK)


def _step_by_step(x, dt, a, b, c, skip):
    """``ssm_scan``'s recurrence one position a step (``lax.scan`` over T,
    float32): what the chunked form is tested against."""
    h, g = x.shape[2], b.shape[2]
    f32 = jnp.float32
    b, c = (jnp.repeat(v.astype(f32), h // g, axis=2) for v in (b, c))
    x, dt, a = x.astype(f32), dt.astype(f32), a.astype(f32)

    def token(state, ops):
        x_t, dt_t, a_t, b_t, c_t = ops
        state = (jnp.exp(a_t)[..., None, None] * state
                 + jnp.einsum("bhp,bhs->bhps", dt_t[..., None] * x_t, b_t))
        return state, jnp.einsum("bhps,bhs->bhp", state, c_t)

    state = jnp.zeros((x.shape[0], h, x.shape[3], b.shape[3]), f32)
    _, y = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, a, b, c)))
    return jnp.moveaxis(y, 0, 1) + skip.astype(f32)[:, None] * x


@pytest.mark.parametrize("rates", [(0.01, 0.1, 0.5, 1.0),
                                   (0.5, 2.0, 8.0, 30.0)],
                         ids=["mild", "underflows"])
def test_chunked_scan_is_the_recurrence_forward_and_backward(rates):
    ops = _operands(3, rates)
    if rates[-1] > 1:
        # a chunk's log-decay is far past float32's range: exp(-G) is inf
        assert float(ops[2].reshape(B, -1, T, H)[0, 0, :CHUNK].sum(0).min()) \
            < -200
    want, got = _step_by_step(*ops), jax.jit(_chunked)(*ops)
    assert got.shape == (B, T, H, P) and bool(jnp.isfinite(got).all())
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-6 * scale
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = [jax.jit(jax.grad(lambda *o, f=f: (f(*o) * w).sum(),
                              argnums=tuple(range(6))))(*ops)
             for f in (_chunked, _step_by_step)]
    for name, mine, theirs in zip("x dt a b c skip".split(), *grads):
        assert bool(jnp.isfinite(mine).all()), name
        assert float(jnp.abs(mine - theirs).max()) \
            < 5e-6 * max(1.0, float(jnp.abs(theirs).max())), name


def test_the_backward_is_the_ops_own_and_keeps_no_chunk_matrix():
    """What crosses from the forward to the backward is the operands and
    the chunks' START states: nothing [chunk, chunk]."""
    ops = _operands(4, (0.1, 0.2, 0.4, 0.8))[:5]
    by = lambda v, *tail: jnp.pad(
        v, ((0, 0), (0, 3)) + ((0, 0),) * (v.ndim - 2)).reshape(
        B, 3, CHUNK, *tail)
    chunks = (by(ops[0], G, H // G, P), by(ops[1], G, H // G),
              by(ops[2], G, H // G), by(ops[3], G, S), by(ops[4], G, S))
    _, kept = ss._scan_fwd(*chunks)
    assert [k.shape for k in kept[:5]] == [c.shape for c in chunks]
    assert kept[5].shape == (B, 3, G, H // G, P, S)
    assert len(kept) == 6


def test_padding_writes_nothing_and_forgets_nothing():
    ops = _operands(5, (0.1, 0.2, 0.4, 0.8))
    whole = jax.jit(_chunked)(*ops)
    short = jax.jit(_chunked)(*(o[:, :32] for o in ops[:5]), ops[5])
    assert float(jnp.abs(whole[:, :32] - short).max()) < 1e-5


def test_conv_silu_with_a_bias_is_the_plain_chain():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 19, 24)).astype(jnp.bfloat16)
    w = jax.random.uniform(k[1], (4, 24), minval=-0.5, maxval=0.5)
    bias = jax.random.uniform(k[2], (24,), minval=-0.5, maxval=0.5)
    got = la.flat_conv_silu(x, w, bias)
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (3, 0), (0, 0)))
    want = jax.nn.silu(sum(xf[:, j:j + 19] * w[j] for j in range(4)) + bias)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 2e-2
    # with no bias it is linear_attention's chain without its l2 norm
    plain = la._chain(x, w.reshape(4, 3, 8), False)
    assert bool((la.flat_conv_silu(x, w) == plain).all())
    assert float(jnp.abs(got.astype(jnp.float32)
                         - plain.astype(jnp.float32)).max()) > 0.05


def test_gated_group_norm_is_its_plain_form_and_not_a_heads_norm():
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    y = jax.random.normal(k[0], (2, 7, 4, 8))            # 4 heads of 8
    z = jax.random.normal(k[1], (2, 7, 32))
    w = 1 + 0.1 * jax.random.normal(k[2], (32,))
    got = ss.gated_group_norm(y, z, w, 2, eps=1e-5)      # 2 groups of 16
    gated = (y.reshape(2, 7, 32) * jax.nn.silu(z)).reshape(2, 7, 2, 16)
    want = (gated / jnp.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 7, 32) * w
    assert got.shape == (2, 7, 32)
    assert float(jnp.abs(got - want).max()) < 1e-5
    by_head = ss.gated_group_norm(y, z, w, 4, eps=1e-5)
    one_group = ss.gated_group_norm(y, z, w, 1, eps=1e-5)
    assert float(jnp.abs(got - by_head).max()) > 0.05
    assert float(jnp.abs(got - one_group).max()) > 0.05


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_group_norms_kernels_are_the_plain_form(dtype, monkeypatch):
    """The linear mixers' norm kernels handed a GROUP (interpreted, ONE
    lane-whole shape: two groups of 256 lanes, a row of 77 that is padded to
    three 32-token tiles, so ``d_weight`` sums over grid steps) against
    ``_group_norm_plain``, today's body: value, ``d_y``, ``d_z``,
    ``d_weight``, each in its operand's shape and dtype; and what they
    compute is neither a heads' norm nor a one-group norm."""
    monkeypatch.setattr(la, "_CONV_TOKENS", 32)
    k = jax.random.split(jax.random.PRNGKey(2), 4)
    y = jax.random.normal(k[0], (2, 77, 8, 64)).astype(dtype)   # 8 heads of 64
    z = jax.random.normal(k[1], (2, 77, 512)).astype(dtype)
    w = 1 + 0.1 * jax.random.normal(k[2], (512,))
    weight = jax.random.normal(k[3], z.shape)

    def both(groups):
        return jax.jit(jax.value_and_grad(
            lambda y, z, w: (ss.gated_group_norm(
                y, z, w, groups, eps=1e-5).astype(jnp.float32) * weight).sum(),
            (0, 1, 2)))(y, z, w)

    plain = both(2)
    monkeypatch.setattr(la, "_one_tpu", lambda a: True)
    monkeypatch.setattr(ss, "_group_norm_plain", None)          # never reached
    assert ss._norm_takes_kernels(z, 256)
    fused = both(2)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(plain)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)
                             ).max()) <= tol * (1 + float(jnp.abs(b).max()))
    assert fused[1][0].shape == y.shape and fused[1][2].shape == w.shape
    out = ss.gated_group_norm(y, z, w, 2, eps=1e-5)
    assert out.shape == z.shape and out.dtype == dtype
    # four groups of 128 lanes and one of 512 are other norms (both kernels')
    for groups in (4, 1):
        other = ss.gated_group_norm(y, z, w, groups, eps=1e-5)
        assert float(jnp.abs(out.astype(jnp.float32)
                             - other.astype(jnp.float32)).max()) > 0.05


def test_step_and_decay_and_the_counter_by_another_chunk():
    w = {"dt_bias": jnp.asarray([-3.0, 0.0]), "A_log": jnp.log(
        jnp.asarray([1.0, 16.0]))}
    raw = jnp.zeros((1, 6, 2))
    step, decay = ss.step_and_decay(raw, w)
    np.testing.assert_allclose(step[0, 0], jax.nn.softplus(w["dt_bias"]),
                               rtol=1e-6)
    np.testing.assert_allclose(decay[0, 0], -step[0, 0] * jnp.asarray(
        [1.0, 16.0]), rtol=1e-6)
    # the most negative sum over any chunk of 3 / of 6 positions
    assert float(la.log_decay_min(decay, 3)) == pytest.approx(
        3 * float(decay[0, 0, 1]), rel=1e-6)
    assert float(la.log_decay_min(decay, 6)) == pytest.approx(
        6 * float(decay[0, 0, 1]), rel=1e-6)


# -- experts without a gate projection ----------------------------------------------

def _experts(seed: int = 0, n=64, d=16, f=24, e=8, top_k=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (1, n, d)),
            jax.random.normal(k[1], (d, e)),
            0.3 * jax.random.normal(k[2], (e, d, f)),
            0.3 * jax.random.normal(k[3], (e, f, d)), top_k)


def _dense(x, router_w, w_up, w_down, top_k, held=None):
    """Every token through every expert, under the router's gates."""
    u = x[0]
    _, gates, chosen = moe.route(u @ router_w, top_k)
    dense = jnp.zeros((u.shape[0], router_w.shape[1])).at[
        jnp.arange(u.shape[0])[:, None], chosen].set(gates)
    first, end = held or (0, router_w.shape[1])
    outs = jnp.einsum("nef,efd->ned", jnp.square(jax.nn.relu(
        jnp.einsum("nd,edf->nef", u, w_up))), w_down)
    return (outs * dense[:, first:end, None]).sum(1)[None]


@pytest.mark.parametrize("held", [None, (0, 2)], ids=["all", "held-buffer"])
def test_experts_without_a_gate_are_two_grouped_matmuls(held, monkeypatch):
    x, router_w, w_up, w_down, top_k = _experts()
    if held is not None:
        monkeypatch.setattr(moe, "_GMM_ROWS", 8)    # a buffer under A rows
        w_up, w_down = w_up[:2], w_down[:2]

    def block(x, w_up, w_down):
        return moe.moe_swiglu_dropless(
            x, router_w, None, w_up, w_down, top_k=top_k, held=held,
            activation="relu2")[0]

    def plain(x, w_up, w_down):
        return _dense(x, router_w, w_up, w_down, top_k, held)

    got, want = block(x, w_up, w_down), plain(x, w_up, w_down)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(jnp.abs(want).max()) > 0.1
    w = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    mine, theirs = (jax.grad(lambda *o, f=f: (f(*o) * w).sum(),
                             argnums=(0, 1, 2))(x, w_up, w_down)
                    for f in (block, plain))
    for a, b in zip(mine, theirs):
        assert float(jnp.abs(a - b).max()) < 1e-3 * max(
            1.0, float(jnp.abs(b).max()))
    # two grouped matmuls forward: no gate's
    counts = jnp.asarray([4, 4], jnp.int32)
    for w_gate, matmuls in ((None, 1), (w_up[:2], 2)):
        fwd = jax.make_jaxpr(lambda rows, w: moe._gated(
            rows, w_gate, w, counts, "relu2"))(x[0, :8], w_up[:2])
        assert str(fwd).count("ragged_dot_general[") == matmuls


def test_shared_expert_without_a_gate_and_relu2():
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    x, w_up, w_down = (jax.random.normal(k[0], (5, 8)),
                       jax.random.normal(k[1], (8, 12)),
                       jax.random.normal(k[2], (12, 8)))
    got = moe.shared_expert(x, None, w_up, w_down, "relu2")
    want = jnp.square(jnp.maximum(x @ w_up, 0)) @ w_down
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert set(moe.ACTIVATIONS) == {"silu", "relu", "relu2"}
    gated = moe.shared_expert(x, w_up, w_up, w_down)       # as it was
    assert float(jnp.abs(
        gated - (jax.nn.silu(x @ w_up) * (x @ w_up)) @ w_down).max()) < 1e-4


def test_lane_whole_pads_the_inner_width_on_a_tpu_alone(monkeypatch):
    w_up, w_down = jnp.ones((2, 8, 200)), jnp.ones((2, 200, 8))
    assert moe._lane_whole(None, w_up, w_down) == (None, w_up, w_down)

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    gate, up, down = moe._lane_whole(None, w_up, w_down)
    assert gate is None and up.shape == (2, 8, 256) and down.shape == (
        2, 256, 8)
    assert float(up[..., 200:].sum()) == 0 and float(down[:, 200:].sum()) == 0
    x = jnp.ones((3, 8))
    want = jnp.square(x @ w_up[0]) @ w_down[0]
    assert float(jnp.abs(jnp.square(x @ up[0]) @ down[0] - want).max()) == 0
    whole = jnp.ones((2, 8, 256)), jnp.ones((2, 256, 8))
    assert moe._lane_whole(whole[0], *whole)[1] is whole[0]
