"""``ray_tpu/ops/linear_attention.py`` on the CPU: the chunked gated delta
rule against the token-by-token recurrence
(``chipbench/reference/kimi_linear.py`` ``delta_rule``: one ``lax.scan``
step a position, no chunks), forward and the gradients of all five
operands, over several chunks, with a row that is no whole number of
chunks, and at the strong end of the decay, where a form
that made ``exp(-G)`` alone would overflow; the short convolution causal
and equal to the reference's; the pieces the chunked form is built from.
Both sides compute in float32 under ``highest`` precision: the tolerances
are float32 rounding, a fault has to miss by 1000 x that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import kimi_linear as reference
from ray_tpu.ops import linear_attention as la

TOL = 1e-5
OPERANDS = ("q", "k", "v", "g", "beta")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def operands(seed: int, t: int, *, b: int = 2, h: int = 3, dk: int = 16,
             dv: int = 8, decay: float = 0.3):
    """q, k l2-normed, v normal, g in (-decay, 0] a channel, beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (la.l2_norm(jax.random.normal(ks[0], (b, t, h, dk))),
            la.l2_norm(jax.random.normal(ks[1], (b, t, h, dk))),
            jax.random.normal(ks[2], (b, t, h, dv)),
            -decay * jax.random.uniform(ks[3], (b, t, h, dk)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))))


def gradients(fn, ops, seed: int = 9):
    w = jax.random.normal(jax.random.PRNGKey(seed), ops[2].shape)
    return jax.jit(jax.grad(lambda *a: (fn(*a) * w).sum(),
                            argnums=range(5)))(*ops)


# jitted: eagerly, the chunk's loops are thousands of dispatches. A test
# that changes CHUNK calls ``la.gated_delta_rule`` itself (the constant is
# read when the function is traced).
rule = jax.jit(la.gated_delta_rule)
recurrence = jax.jit(reference.delta_rule)


def worst(a, b) -> float:
    return float(jnp.abs(a - b).max())


# one chunk; several chunks; a row that is no whole number of chunks (200 =
# 3 chunks + 8, padded); many chunks; fewer positions than a chunk
@pytest.mark.parametrize("t", [64, 192, 200, 35 * 64, 24])
def test_the_chunked_rule_is_the_recurrence_forward(t):
    ops = operands(t, t)
    o, want = rule(*ops), recurrence(*ops)
    assert o.shape == want.shape == (2, t, 3, 8)
    assert worst(o, want) < TOL
    assert float(jnp.abs(want).max()) > 0.1


@functools.cache
def _both_gradients():
    """Over 3 chunks + 8 positions (padded to 4 chunks)."""
    with jax.default_matmul_precision("highest"):
        ops = operands(201, 200)
        return (ops, gradients(la.gated_delta_rule, ops),
                gradients(reference.delta_rule, ops))


@pytest.mark.parametrize("name", OPERANDS)
def test_the_chunked_rules_own_backward_is_the_recurrences_gradient(name):
    _, got, want = _both_gradients()
    i = OPERANDS.index(name)
    assert float(jnp.abs(want[i]).max()) > 0.05
    assert worst(got[i], want[i]) < 10 * TOL


def test_the_chunk_is_memorys_not_the_arithmetics(monkeypatch):
    """Chunks of 32 positions (two sub-blocks, one merge of the inverse)
    give what chunks of 64 give: forward and reverse."""
    ops, _, want = _both_gradients()
    monkeypatch.setattr(la, "CHUNK", 32)
    halved = lambda *a: la.gated_delta_rule(*a)      # traced anew
    assert worst(jax.jit(halved)(*ops), recurrence(*ops)) < TOL
    for a, b in zip(gradients(halved, ops), want):
        assert worst(a, b) < 10 * TOL


@pytest.mark.parametrize("decay", [1.6, 6.0])
def test_the_strong_end_of_the_decay_is_finite_and_the_recurrence(decay):
    """``|g|`` 1.6 a token on EVERY channel over whole chunks (the model's
    own init reaches it: 102 over a chunk, where float32's ``exp`` ends at
    88), and 6 a token (384 over a chunk): every value finite, forward and
    (at 1.6) gradients the recurrence's."""
    q, k, v, g, beta = operands(5, 2 * 64, decay=decay)
    ops = (q, k, v, jnp.full_like(g, -decay), beta)
    assert float(la.log_decay_min(ops[3])) == pytest.approx(-64 * decay)
    o, want = rule(*ops), recurrence(*ops)
    assert bool(jnp.isfinite(o).all()) and worst(o, want) < TOL
    if decay == 1.6:
        for got, ref in zip(gradients(la.gated_delta_rule, ops),
                            gradients(reference.delta_rule, ops)):
            assert bool(jnp.isfinite(got).all())
            assert worst(got, ref) < 10 * TOL
    # mixed: one head forgets at once, its neighbour not at all
    g = g.at[:, :, 0].set(-decay).at[:, :, 1].set(0.0)
    ops = (q, k, v, g, beta)
    assert worst(rule(*ops), recurrence(*ops)) < TOL


def test_no_decay_and_full_steps_on_repeated_keys_stay_exact():
    """The worst case of the triangular inverse: the SAME key at every
    position, ``beta`` = 1, no decay (``I + A`` is all ones below the
    diagonal; a Neumann series of it reaches 1e18). The state then holds
    only the last value."""
    t = 128
    q, k, v, g, beta = operands(7, t, b=1, h=1)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    ops = (k, k, v, jnp.zeros_like(g), jnp.ones_like(beta))
    o = rule(*ops)
    assert worst(o, recurrence(*ops)) < TOL
    assert worst(o, v / 4.0) < TOL          # S^T k = the last v; / sqrt(16)


def test_the_triangular_inverse_is_the_inverse():
    m = 0.1 * jnp.tril(jax.random.normal(jax.random.PRNGKey(0),
                                         (2, 3, 64, 64)), -1)
    inv = jax.jit(la._block_lower_inverse)(m)
    eye = jnp.eye(64)
    assert worst(inv, jnp.linalg.inv(eye + m)) < 10 * TOL
    assert worst(jnp.einsum("...ij,...jk->...ik", eye + m, inv),
                 jnp.broadcast_to(eye, m.shape)) < 10 * TOL
    small = m[..., :16, :16] * 0.3
    assert worst(jax.jit(la._unit_lower_inverse)(small),
                 jnp.linalg.inv(jnp.eye(16) + small)) < TOL
    # what lies on or above the diagonal is not read
    assert worst(jax.jit(la._block_lower_inverse)(
        m + jnp.triu(jnp.ones((64, 64)))), inv) == 0.0


def test_later_tokens_change_no_earlier_output():
    """Causal, end to end: the convolution, and the rule behind it."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 3, 16))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 3, 16))
    y = la.short_conv(x, w)
    changed = x.at[:, 25:].set(jax.random.normal(jax.random.PRNGKey(3),
                                                 x[:, 25:].shape))
    y2 = la.short_conv(changed, w)
    assert worst(y[:, :25], y2[:, :25]) == 0.0
    assert float(jnp.abs(y[:, 25:] - y2[:, 25:]).min()) > 0.0
    ops = operands(11, 200)
    late = tuple(a.at[:, 150:].set(a[:, 150:] * 0.5) for a in ops)
    assert worst(rule(*ops)[:, :150],
                 rule(*late)[:, :150]) == 0.0


def test_the_short_convolution_is_the_references_and_numpys():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 19, 3, 8))
    w = jax.random.normal(jax.random.PRNGKey(5), (4, 3, 8))
    y = np.asarray(la.short_conv(x, w))
    assert worst(y, reference.conv4(x, w)) < TOL
    xn, wn = np.asarray(x, np.float64), np.asarray(w, np.float64)
    for t in (0, 1, 2, 3, 18):       # the token and the three before it
        want = sum(wn[3 - back] * xn[:, t - back] for back in range(4)
                   if t - back >= 0)
        assert np.abs(y[:, t] - want).max() < TOL
    flat = la.short_conv(x.reshape(2, 19, 24), w.reshape(4, 24))   # [B, T, C]
    assert worst(flat.reshape(y.shape), y) == 0.0
    assert la.short_conv(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16


def test_the_counter_is_the_most_negative_sum_inside_a_chunk():
    g = -jax.random.uniform(jax.random.PRNGKey(6), (2, 200, 3, 16))
    padded = np.zeros((2, 256, 3, 16))
    padded[:, :200] = np.asarray(g)
    want = padded.reshape(2, 4, 64, 3, 16).sum(2).min()
    assert float(la.log_decay_min(g)) == pytest.approx(want, rel=1e-5)
    assert float(jax.grad(lambda g: la.log_decay_min(g))(g).sum()) == 0.0


def test_bfloat16_operands_keep_a_float32_state():
    """The train step's dtypes: bfloat16 q, k, v, float32 g and beta ->
    bfloat16 out, within bfloat16's rounding of the float32 recurrence,
    and gradients in each operand's own dtype."""
    ops = operands(13, 128)
    half = tuple(a.astype(jnp.bfloat16) for a in ops[:3]) + ops[3:]
    exact = tuple(a.astype(jnp.float32) for a in half)
    o = rule(*half)
    want = recurrence(*exact)
    assert o.dtype == jnp.bfloat16
    assert float(jnp.abs(o.astype(jnp.float32) - want).mean()) < \
        0.02 * float(jnp.abs(want).mean())
    grads = gradients(lambda *a: la.gated_delta_rule(*a).astype(jnp.float32),
                      half)
    assert [a.dtype for a in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    for got, ref in zip(grads, gradients(reference.delta_rule, exact)):
        assert float(jnp.abs(got.astype(jnp.float32) - ref).mean()) < \
            0.03 * float(jnp.abs(ref).mean())
