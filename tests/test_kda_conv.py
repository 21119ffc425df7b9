"""A KDA layer's convolution chains as fused passes over FLAT operands
(``ray_tpu/ops/linear_attention.py`` ``conv_silu``, ``_chain_kernels``): the
Pallas kernels, interpreted on the CPU at tiny widths but 128-wide heads,
against ``_chain``, the float32 chain in plain XLA that runs wherever the
kernels do not. Forward: the same float32 arithmetic in the same order,
rounded once (bit-equal from float32 inputs, within one bfloat16 ulp from
bfloat16). Backward: ``dx`` and ``dw`` against ``jax.grad`` of ``_chain``.
The token tiles' edges: a row that is no whole number of tiles, a row shorter
than one, the halo (what a tile reads of the tile ahead of it, forward, and
of the tile behind it, backward), and batch rows that must not leak into each
other. The chain WITH A BIAS row (a state-space layer's, ``flat_conv_silu``)
against ``_chain(x, w, False, bias)`` at one lane-whole shape: value, ``dx``,
``dw``, ``d_bias``, the halo both ways, and the rule. Nothing here is a speed.
"""

from __future__ import annotations

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import linear_attention as la

F32 = jnp.float32
D = 128         # the kernels' head width
TILE = 32       # tokens a grid step here: the cell's 512 would make the
                # interpreter walk the same code over more rows


@pytest.fixture(autouse=True)
def small_tiles():
    with mock.patch.object(la, "_CONV_TOKENS", TILE):
        yield


def _case(t: int, taps: int, dtype, *, b: int = 2, heads: int = 3, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (b, t, heads * D)).astype(dtype)
    w = jax.random.uniform(ks[1], (taps, heads, D), minval=-0.5, maxval=0.5)
    # the weight of a sum IS y's gradient, which comes in y's dtype: values
    # bfloat16 holds, so that both dtypes' chains are given the same
    weight = jax.random.normal(ks[2], (b, t, heads * D)).astype(jnp.bfloat16)
    return x, w, weight.astype(F32)


# both sides compiled, as a step runs them: op by op XLA's CPU backend rounds
# every product where a compiled chain's multiply-adds keep theirs
fused_chain = jax.jit(la._chain_kernels, static_argnums=2)
plain_chain = jax.jit(la._chain, static_argnums=2)


def _ulp(y):
    """The spacing of ``y``'s dtype at each of its values."""
    yf = jnp.abs(y.astype(F32))
    return jnp.maximum(yf, 1e-30) * float(jnp.finfo(y.dtype).eps)


# T: 3 tiles; 2 tiles and 13 tokens; shorter than a tile; one token
LENGTHS = [96, 77, 20, 1]


@pytest.mark.parametrize("norm", [True, False], ids=["l2", "plain"])
@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("t", LENGTHS)
def test_the_fused_forward_is_the_float32_chain_rounded_once(t, taps, norm):
    x, w, _ = _case(t, taps, F32)
    fused, chain = fused_chain(x, w, norm), plain_chain(x, w, norm)
    assert fused.shape == x.shape and fused.dtype == F32
    assert float(jnp.abs(fused - chain).max()) == 0.0
    xb = x.astype(jnp.bfloat16)
    fused, chain = fused_chain(xb, w, norm), plain_chain(xb, w, norm)
    assert fused.dtype == jnp.bfloat16
    assert bool((jnp.abs(fused.astype(F32) - chain.astype(F32))
                 <= _ulp(chain)).all())


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("norm", [True, False], ids=["l2", "plain"])
@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("t", LENGTHS[:3])
def test_dx_and_dw_are_the_chains_gradients(t, taps, norm, dtype):
    """Against ``jax.grad`` of the float32 chain at the same values: from
    bfloat16 inputs autodiff of ``_chain`` rounds ``dx`` once a TAP (the
    gradient of each shifted slice's cast), the fused backward once."""
    x, w, weight = _case(t, taps, dtype)
    loss = lambda chain: lambda x, w: (  # noqa: E731
        chain(x, w, norm).astype(F32) * weight).sum()
    dx, dw = jax.jit(jax.grad(loss(la._chain_kernels), (0, 1)))(x, w)
    ref_dx, ref_dw = jax.jit(jax.grad(loss(la._chain), (0, 1)))(
        x.astype(F32), w)
    assert (dx.shape, dx.dtype) == (x.shape, x.dtype)
    # dw: float32 sums over B and T, whatever x's dtype
    assert (dw.shape, dw.dtype) == (w.shape, F32)
    near = 2e-6 if dtype == F32 else 2e-2       # y's rounding is in ref_dw
    assert float(jnp.abs(dw - ref_dw).max()) <= near * float(
        jnp.abs(ref_dw).max())
    if dtype == F32:
        assert float(jnp.abs(dx - ref_dx).max()) <= 2e-6 * float(
            jnp.abs(ref_dx).max())
    else:       # rounded once, to x's dtype: the reference's rounding
        assert bool((jnp.abs(dx.astype(F32) - ref_dx)
                     <= _ulp(ref_dx.astype(dtype)) + 1e-6).all())


@pytest.mark.parametrize("norm", [True, False], ids=["l2", "plain"])
@pytest.mark.parametrize("taps", [4, 2])
def test_an_impulse_at_a_tiles_last_row_reaches_the_next_taps_rows(taps, norm):
    """Forward, across the tiles' edge: the token at a tile's last row is
    read by itself and by the first K - 1 tokens of the NEXT tile, by no
    other, and by nothing in the other batch row."""
    b, t, heads = 2, 3 * TILE, 2
    at = TILE - 1
    x = jnp.zeros((b, t, heads * D), F32).at[0, at].set(1.0)
    w = jnp.full((taps, heads, D), 0.25, F32)
    y = np.asarray(fused_chain(x, w, norm))
    touched = np.abs(y).max(-1) > 0                             # [B, T]
    assert touched[0].nonzero()[0].tolist() == list(range(at, at + taps))
    assert not touched[1].any()
    assert float(np.abs(y - np.asarray(plain_chain(x, w, norm))).max()) == 0.0


@pytest.mark.parametrize("norm", [True, False], ids=["l2", "plain"])
@pytest.mark.parametrize("taps", [4, 2])
def test_a_gradient_at_a_tiles_first_row_reaches_the_taps_rows_ahead(taps,
                                                                      norm):
    """Backward, the other way: ``dy`` at a tile's FIRST row alone reaches
    ``dx`` of that row and of the last K - 1 rows of the tile ahead of it
    (the scratch the backward carries from the tile behind), and no other
    batch row."""
    x, w, _ = _case(3 * TILE, taps, F32, heads=2, seed=4)
    at = 2 * TILE
    dy = jnp.zeros(x.shape, F32).at[1, at].set(1.0)
    back = lambda chain: jax.vjp(  # noqa: E731
        lambda x: chain(x, w, norm), x)[1](dy)[0]
    dx, ref = np.asarray(back(la._chain_kernels)), np.asarray(back(la._chain))
    touched = np.abs(dx).max(-1) > 0
    assert touched[1].nonzero()[0].tolist() == list(
        range(at - taps + 1, at + 1))
    assert not touched[0].any()
    assert float(np.abs(dx - ref).max()) <= 2e-6 * float(np.abs(ref).max())


def test_the_first_rows_of_every_batch_row_read_zeros_ahead_of_them():
    """Nothing crosses from one batch row into the next: row 1 of a batch
    of two is what it is alone, bit for bit, first tokens included."""
    x, w, _ = _case(2 * TILE + 5, 4, F32, seed=2)
    both = fused_chain(x, w, True)
    alone = fused_chain(x[1:], w, True)
    assert float(jnp.abs(both[1:] - alone).max()) == 0.0


def _flat_mixer_operands(heads: int, d: int, taps: int, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    flat = [jax.random.normal(k, (2, 40, heads * d)).astype(dtype)
            for k in ks[:3]]
    taps_of = [jax.random.uniform(k, (taps, heads, d), minval=-0.5,
                                  maxval=0.5) for k in ks[3:]]
    return flat, taps_of


def _by_heads(q, k, v, w_q, w_k, w_v):
    """``conv_silu`` as the parent made it, operands [B, T, H, d]."""
    return (la.l2_norm(jax.nn.silu(la._conv(q, w_q))).astype(q.dtype),
            la.l2_norm(jax.nn.silu(la._conv(k, w_k))).astype(k.dtype),
            jax.nn.silu(la._conv(v, w_v)).astype(v.dtype))


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("d,fused", [(16, False), (D, False), (D, True)],
                         ids=["16-wide", "128-wide", "128-wide-kernels"])
def test_conv_silu_returns_the_same_flat_arrays_either_way(d, fused, taps,
                                                           dtype):
    """``conv_silu`` on the CPU is the fallback (``_on_one_tpu`` false);
    steered to the kernels it returns the same flat arrays, and both are
    the parent's by-heads chains viewed flat."""
    heads = 3
    flat, taps_of = _flat_mixer_operands(heads, d, taps, dtype)
    chosen = []
    with mock.patch.object(la, "_on_one_tpu",
                           lambda *a: chosen.append(a[1:]) or fused):
        got = jax.jit(lambda *a: la.conv_silu(*a))(*flat, *taps_of)  # anew
    assert chosen == [(d, d)]       # asked once, of the heads' widths
    parents = jax.jit(_by_heads)(
        *(a.reshape(2, 40, heads, d) for a in flat), *taps_of)
    for y, ref, x in zip(got, parents, flat):
        assert y.shape == x.shape and y.dtype == x.dtype
        ref = ref.reshape(x.shape).astype(F32)
        # by heads XLA orders a head's sum otherwise: float32's rounding
        assert bool((jnp.abs(y.astype(F32) - ref) <= _ulp(y) + 1e-7).all())


def test_more_taps_than_the_halo_holds_take_the_plain_chain():
    flat, taps_of = _flat_mixer_operands(2, D, la._CONV_HALO + 2, F32)
    with mock.patch.object(la, "_on_one_tpu", lambda *a: True), \
            mock.patch.object(la, "_chain_kernels",
                              lambda *a: pytest.fail("the kernels ran")):
        got = jax.jit(lambda *a: la.conv_silu(*a))(*flat, *taps_of)  # anew
    assert float(jnp.abs(got[2] - plain_chain(flat[2], taps_of[2],
                                              False)).max()) == 0.0


@pytest.mark.parametrize("norm", [True, False], ids=["l2", "plain"])
def test_the_cells_own_tiles_give_the_same(norm):
    """The tile sizes a train step runs (the fixture's small ones aside):
    two tiles of ``_CONV_TOKENS``, the second mostly padding, each walked in
    blocks of ``_CONV_ROWS``."""
    with mock.patch.object(la, "_CONV_TOKENS", 1024):
        t = la._CONV_TOKENS + 6
        x, w, weight = _case(t, 4, F32, b=1, heads=4, seed=9)
        assert la._conv_tokens(t) == 1024 and 1024 % la._CONV_ROWS == 0
        loss = lambda chain: lambda x, w: (  # noqa: E731
            chain(x, w, norm) * weight).sum()
        (y, (dx, dw)), (ref, (ref_dx, ref_dw)) = (
            (jax.jit(lambda x, w: chain(x, w, norm))(x, w),
             jax.jit(jax.grad(loss(chain), (0, 1)))(x, w))
            for chain in (la._chain_kernels, la._chain))
    assert float(jnp.abs(y - ref).max()) == 0.0
    for got, want in ((dx, ref_dx), (dw, ref_dw)):
        assert float(jnp.abs(got - want).max()) <= 2e-6 * float(
            jnp.abs(want).max())


# -- the chain with a bias row: a state-space layer's (``flat_conv_silu``) ----

def _bias_case(dtype, t: int = 2 * TILE + 13, c: int = 3 * D, seed: int = 11):
    """``[K, C]`` taps (no heads) and a bias at ONE lane-whole shape: two
    tiles and 13 tokens of three 128-lane tiles."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (2, t, c)).astype(dtype)
    w = jax.random.uniform(ks[1], (4, c), minval=-0.5, maxval=0.5)
    bias = jax.random.uniform(ks[2], (c,), minval=-0.5, maxval=0.5)
    weight = jax.random.normal(ks[3], (2, t, c)).astype(jnp.bfloat16)
    return x, w, bias, weight.astype(F32)


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_chain_with_a_bias_is_the_plain_chain_and_its_gradients(dtype):
    """Value, ``dx``, ``dw`` and ``d_bias`` against ``_chain(x, w, False,
    bias)``: the forward bit-equal from float32 and within an ulp from
    bfloat16, the gradients as ``test_dx_and_dw_are_the_chains_gradients``
    holds the chains without one; ``d_bias`` float32 in the bias' shape."""
    x, w, bias, weight = _bias_case(dtype)
    fused, chain = (jax.jit(lambda *a, f=f: f(a[0], a[1], False, a[2]))(
        x, w, bias) for f in (la._chain_kernels, la._chain))
    assert (fused.shape, fused.dtype) == (x.shape, x.dtype)
    if dtype == F32:
        assert float(jnp.abs(fused - chain).max()) == 0.0
    assert bool((jnp.abs(fused.astype(F32) - chain.astype(F32))
                 <= _ulp(chain)).all())
    # the bias is felt: the chain without it is another
    assert float(jnp.abs(chain.astype(F32) - plain_chain(x, w, False).astype(
        F32)).max()) > 0.05
    loss = lambda f: lambda x, w, b: (  # noqa: E731
        f(x, w, False, b).astype(F32) * weight).sum()
    dx, dw, db = jax.jit(jax.grad(loss(la._chain_kernels), (0, 1, 2)))(
        x, w, bias)
    ref_dx, ref_dw, ref_db = jax.jit(jax.grad(loss(la._chain), (0, 1, 2)))(
        x.astype(F32), w, bias)
    assert (dx.shape, dx.dtype) == (x.shape, x.dtype)
    assert (dw.shape, dw.dtype) == (w.shape, F32)
    assert (db.shape, db.dtype) == (bias.shape, F32)
    near = 2e-6 if dtype == F32 else 2e-2       # y's rounding is in the sums
    for got, want in ((dw, ref_dw), (db, ref_db)):
        assert float(jnp.abs(got - want).max()) <= near * float(
            jnp.abs(want).max())
    if dtype == F32:
        assert float(jnp.abs(dx - ref_dx).max()) <= 2e-6 * float(
            jnp.abs(ref_dx).max())
    else:
        assert bool((jnp.abs(dx.astype(F32) - ref_dx)
                     <= _ulp(ref_dx.astype(dtype)) + 1e-6).all())


def test_the_halo_with_a_bias_forward_and_backward():
    """An impulse at a tile's last row reaches the next tile's first K - 1
    rows THROUGH the bias (every row reads ``silu(bias)`` at least, so it is
    the difference from the chain of zeros that is confined); a gradient at
    a tile's first row reaches the K - 1 rows ahead of it, and ``d_bias``
    is that row's ``dz`` alone."""
    taps, c = 4, 2 * D
    _, w, bias, _ = _bias_case(F32, c=c)
    fused = jax.jit(lambda x: la._chain_kernels(x, w, False, bias))
    plain = jax.jit(lambda x: la._chain(x, w, False, bias))
    at = TILE - 1
    zeros = jnp.zeros((2, 3 * TILE, c), F32)
    x = zeros.at[0, at].set(1.0)
    moved = np.abs(np.asarray(fused(x) - fused(zeros))).max(-1) > 0
    assert moved[0].nonzero()[0].tolist() == list(range(at, at + taps))
    assert not moved[1].any()
    assert float(jnp.abs(fused(x) - plain(x)).max()) == 0.0
    # every row of the chain of zeros is silu(bias): the bias reached them
    assert float(jnp.abs(fused(zeros) - jax.nn.silu(bias)).max()) == 0.0
    x = jax.random.normal(jax.random.PRNGKey(5), zeros.shape)
    at = 2 * TILE
    dy = zeros.at[1, at].set(1.0)
    back = lambda chain: jax.vjp(  # noqa: E731
        lambda x, b: chain(x, w, False, b), x, bias)[1](dy)
    (dx, db), (ref_dx, ref_db) = back(la._chain_kernels), back(la._chain)
    touched = np.abs(np.asarray(dx)).max(-1) > 0
    assert touched[1].nonzero()[0].tolist() == list(
        range(at - taps + 1, at + 1))
    assert not touched[0].any()
    for got, want in ((dx, ref_dx), (db, ref_db)):
        assert float(jnp.abs(got - want).max()) <= 2e-6 * float(
            jnp.abs(want).max())
    assert float(jnp.abs(db).max()) > 0


@pytest.mark.parametrize("taken", [True, False], ids=["one_tpu", "elsewhere"])
def test_flat_conv_silu_asks_one_tpu_and_its_own_shapes(taken):
    """``flat_conv_silu`` takes the kernels on one TPU chip at whole 128-lane
    tiles and taps the halo holds; a CPU, 96 lanes over a tile or nine taps
    take the plain chain. Either way the same flat array."""
    x, w, bias, _ = _bias_case(F32, t=40)
    ran = []
    run = lambda name, f: lambda *a: ran.append(name) or f(*a)  # noqa: E731
    with mock.patch.object(la, "_one_tpu", lambda a: taken), \
            mock.patch.object(la, "_chain_kernels",
                              run("kernels", la._chain_kernels)), \
            mock.patch.object(la, "_chain", run("plain", la._chain)):
        got = jax.jit(lambda *a: la.flat_conv_silu(*a))(x, w, bias)  # anew
        assert ran == ["kernels" if taken else "plain"]
        la.flat_conv_silu(x[..., :D + 96], w[:, :D + 96], bias[:D + 96])
        la.flat_conv_silu(x, jnp.tile(w, (3, 1))[:la._CONV_HALO + 2], bias)
        la.flat_conv_silu(x, w)                     # no bias: the same rule
    assert ran[1:] == ["plain", "plain", "kernels" if taken else "plain"]
    assert float(jnp.abs(got - plain_chain(x, w, False, bias)).max()) == 0.0
