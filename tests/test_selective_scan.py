"""``ops/state_space.py`` ``selective_scan`` (Mamba-1: a decay a channel AND
a state index) on the CPU in float32: the chunked form with its own
backward against the recurrence token by token and ``jax.grad`` of it, for
the output and the gradient of all six operands, at a ragged length, at a
length under one chunk, at whole chunks, and under decays past float32's
range; what its residuals are; that the op rounds once."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import state_space

B, C, N, CHUNK = 2, 12, 4, 8


def recurrence(x, delta, a, b, c, skip):
    """One ``lax.scan`` step a position on a [B, C, N] state."""
    def token(h, ops):
        x_t, d_t, b_t, c_t = ops
        h = (jnp.exp(d_t[..., None] * a) * h
             + (d_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bcn,bn->bc", h, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((*x.shape[::2], a.shape[1])),
                        tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1) + skip * x


def operands(t: int, step: float = 1.0, seed: int = 0):
    k = jax.random.split(jax.random.PRNGKey(seed + t), 7)
    return (jax.random.normal(k[0], (B, t, C)),
            jax.nn.softplus(jax.random.normal(k[1], (B, t, C))) * step,
            -jnp.exp(jax.random.normal(k[2], (C, N))),
            jax.random.normal(k[3], (B, t, N)),
            jax.random.normal(k[4], (B, t, N)),
            jax.random.normal(k[5], (C,))), jax.random.normal(k[6], (B, t, C))


def both(fn, ops, weights):
    return jax.value_and_grad(lambda *o: (fn(*o) * weights).sum(),
                              argnums=tuple(range(6)))(*ops)


# ragged (4 chunks and 5 positions), under one chunk, whole chunks
@pytest.mark.parametrize("t", [37, 5, 64])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(t):
    ops, weights = operands(t)
    chunked = lambda *o: state_space.selective_scan(*o, chunk=CHUNK)
    y, y_ref = chunked(*ops), recurrence(*ops)
    assert y.shape == (B, t, C) and float(jnp.abs(y_ref).max()) > 1
    assert float(jnp.abs(y - y_ref).max()) < 1e-5
    (value, grads), (want, want_grads) = (both(chunked, ops, weights),
                                          both(recurrence, ops, weights))
    assert float(value) == pytest.approx(float(want), rel=1e-5)
    for name, mine, ref in zip("x delta A B C D".split(), grads, want_grads):
        size = float(jnp.abs(ref).max())
        assert size > 0, name
        assert float(jnp.abs(mine - ref).max()) < 2e-5 * size, name


def test_decays_past_float32s_range_stay_finite_and_right():
    """A step of 200 and ``|A|`` up to 20 a token: ``exp(-Delta A)`` would
    overflow at the first position. Every exponent the op takes is <= 0, so
    nothing overflows; the state forgets at once and ``A``'s gradient is a
    difference of near-equal sums, held to the size of its parts."""
    ops, weights = operands(40, step=200.0)
    assert float((ops[1].max() * -ops[2].min())) > 1000     # past exp's 88
    chunked = lambda *o: state_space.selective_scan(*o, chunk=CHUNK)
    (value, grads), (want, want_grads) = (both(chunked, ops, weights),
                                          both(recurrence, ops, weights))
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    assert float(value) == pytest.approx(float(want), rel=1e-5)
    for name, mine, ref, tol in zip("x delta A B C D".split(), grads,
                                    want_grads,
                                    (1e-5, 1e-3, 2e-2, 1e-5, 1e-5, 1e-5)):
        assert float(jnp.abs(mine - ref).max()) <= tol * float(
            jnp.abs(ref).max()) + 1e-6, name


def test_the_residuals_are_the_operands_and_the_chunk_start_states():
    """Nothing [T, channels, state] is kept from the forward to the
    backward: the operands and one state a CHUNK."""
    ops, _ = operands(64)
    x, delta, a, b, c, _ = ops
    y, kept = state_space._selective_fwd(x, delta, a.T, b, c, CHUNK)
    assert [v.shape for v in kept] == [
        x.shape, delta.shape, (N, C), b.shape, c.shape,
        (B, 64 // CHUNK, N, C)]
    assert kept[-1].dtype == jnp.float32 and y.dtype == jnp.float32
    assert float(jnp.abs(kept[-1][:, 0]).max()) == 0        # H_0 = 0
    # a chunk's start state is the recurrence's state at its first position
    _, first = state_space._selective_fwd(x[:, :CHUNK], delta[:, :CHUNK],
                                          a.T, b[:, :CHUNK], c[:, :CHUNK], 4)
    assert first[-1].shape == (B, 2, N, C)


def test_the_op_computes_in_float32_and_rounds_once():
    ops, _ = operands(24)
    x, delta, a, b, c, skip = ops
    half = lambda v: v.astype(jnp.bfloat16)
    y = state_space.selective_scan(half(x), delta, a, half(b), half(c), skip,
                                   chunk=CHUNK)
    assert y.dtype == jnp.bfloat16
    exact = recurrence(half(x).astype(jnp.float32), delta, a,
                       half(b).astype(jnp.float32),
                       half(c).astype(jnp.float32), skip)
    assert float(jnp.abs(y.astype(jnp.float32) - half(exact).astype(
        jnp.float32)).max()) <= float(jnp.abs(exact).max()) * 2 ** -8
