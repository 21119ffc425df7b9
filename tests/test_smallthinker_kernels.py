"""The sliding window in ``ops/attention.py``, which SmallThinker's
windowed layers brought (PR 31): the three Pallas kernels in interpret
mode against the masked reference in forward, dq and dk / dv, at sizes
that have skipped, crossed and full tiles; every other attention path
under the same window; and a count of the tiles each kernel visits,
against the mask itself and against the grids the ``pallas_call``s get.
A file of its own beside ``tests/test_smallthinker.py`` so that the two
run on two workers. CPU only, float32."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

attention = importlib.import_module("ray_tpu.ops.attention")


def _qkvg(t, h=2, d=32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (1, t, h, d), jnp.float32) for k in keys]


# T 1024 in tiles of 128 x 128 under a window of 300 keys: of the 36 tiles
# at or under the diagonal 26 hold a visible pair (10 are SKIPPED), 19 of
# those are CROSSED by the diagonal or the window's edge, 7 are FULL.
KERNEL_CASES = [(1024, 128, 128, 300), (1024, 256, 128, 129),
                (1024, 128, 256, 256), (512, 128, 128, 1), (512, 128, 128, 511)]


@pytest.mark.parametrize("t,block_q,block_k,window", KERNEL_CASES)
def test_windowed_kernels_equal_the_masked_reference(t, block_q, block_k,
                                                     window):
    q, k, v, g = _qkvg(t)
    want = attention.dot_product_attention(q, k, v, causal=True, window=window)
    got = attention.flash_attention(q, k, v, True, block_q, block_k, window)
    assert float(jnp.abs(got - want).max()) < 5e-6
    want_g = jax.grad(lambda *a: (attention.dot_product_attention(
        *a, causal=True, window=window) * g).sum(), (0, 1, 2))(q, k, v)
    got_g = jax.grad(lambda *a: (attention.flash_attention(
        *a, True, block_q, block_k, window) * g).sum(), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got_g, want_g):
        assert float(jnp.abs(a - b).max()) < 2e-5, name
    visits = _tile_visits(t, block_q, block_k, window)
    if (t, block_q, block_k, window) == KERNEL_CASES[0]:
        assert visits == {"steps": (32, 32), "computed": 26, "masked": 19}


@pytest.mark.parametrize("window", [None, 1, 100, 256, 1000])
def test_every_attention_path_takes_the_window(window):
    q, k, v, _ = _qkvg(512, seed=1)
    want = attention.dot_product_attention(q, k, v, causal=True, window=window)
    mask = np.tril(np.ones((512, 512), bool))
    if window is not None:
        mask &= ~np.tril(np.ones((512, 512), bool), -window)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k)) / 32 ** 0.5
    s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    by_hand = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True),
                        np.asarray(v))
    assert np.abs(np.asarray(want) - by_hand).max() < 5e-6
    for impl in ("blockwise", "flash", "auto"):
        got = attention.attention(q, k, v, impl=impl, window=window)
        assert float(jnp.abs(got - want).max()) < 5e-6, impl
    blocked = attention.causal_blocked_attention(q, k, v, block_q=128,
                                                 window=window)
    assert float(jnp.abs(blocked - want).max()) < 5e-6
    with pytest.raises(ValueError, match="sliding window"):
        attention.attention(q, k, v, causal=False, window=window or 4)


def _tile_visits(t, bq, bk, window):
    """What the three causal kernels do at these tiles, from the pieces
    they run by (``_flash_inner``, ``_visible_blocks``, ``_tile_is_full``):
    ``steps`` (grid steps a head: the forward's and dq's, then dk /
    dv's), ``computed`` (tiles whose arithmetic runs) and ``masked``
    (those of them that build a mask: every computed tile with no
    window, only the tiles the diagonal or the window's edge crosses
    with one)."""
    n_q, n_k = t // bq, t // bk
    if window is None:
        computed = sum(min(n_k, (i * bq + bq - 1) // bk + 1)
                       for i in range(n_q))
        return {"steps": (n_q * n_k, n_k * n_q), "computed": computed,
                "masked": computed}
    rows = attention._flash_inner(window, bq, bk, n_q, n_k, True)[0] * n_q
    cols = attention._flash_inner(window, bk, bq, n_k, n_q, False)[0] * n_k
    computed = masked = 0
    for i in range(n_q):
        first, last = attention._visible_blocks(
            i, bq, bk, n_k, *attention._window_reach(window, True))
        for j in range(first, last + 1):
            computed += 1
            masked += not attention._tile_is_full(i, j, bq, bk, window)
    return {"steps": (rows, cols), "computed": computed, "masked": masked}


def _brute_tiles(t, bq, bk, window):
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    visible = (j <= i) if window is None else (j <= i) & (i - j < window)
    tiles = visible.reshape(t // bq, bq, t // bk, bk).transpose(0, 2, 1, 3)
    some, every = tiles.any((2, 3)), tiles.all((2, 3))
    return int(some.sum()), int((some & ~every).sum()), some


@pytest.mark.parametrize("t,bq,bk,window", [
    (1024, 128, 128, 300), (1024, 256, 128, 129), (1024, 128, 256, 256),
    (2048, 256, 256, 512), (2048, 512, 256, 2047), (1024, 128, 128, None)])
def test_the_tiles_each_kernel_visits(t, bq, bk, window, request):
    """``_tile_visits`` (the kernels' own pieces) against a count made
    from the mask itself: the tiles computed are exactly those with a
    visible pair, those that build a mask exactly the ones not wholly
    visible, and the grids the ``pallas_call``s are launched with are as
    long as the count says: the forward's and the one backward kernel's,
    which walks dk / dv's grid (PR 38), and the three of the two-kernel
    form."""
    computed, crossed, some = _brute_tiles(t, bq, bk, window)
    visits = _tile_visits(t, bq, bk, window)
    assert visits["computed"] == computed
    assert visits["masked"] == (crossed if window is not None else computed)
    n_q, n_k = t // bq, t // bk
    if window is not None:
        assert visits["steps"] == (n_q * int(some.sum(1).max()),
                                   n_k * int(some.sum(0).max()))
        assert visits["steps"][0] < n_q * n_k or window > t - bq
    q, k, v, g = _qkvg(t, h=1, d=8)

    def grids():
        jaxpr = jax.make_jaxpr(jax.grad(lambda *a: (attention.flash_attention(
            *a, True, bq, bk, window) * g).sum(), (0, 1, 2)))(q, k, v)
        return [e.params["grid_mapping"].grid for e in _eqns(jaxpr.jaxpr)
                if e.primitive.name == "pallas_call"]

    fwd, bwd = grids()
    assert fwd[1] * fwd[2] == visits["steps"][0]
    assert bwd[1] * bwd[2] == visits["steps"][1]
    request.getfixturevalue("two_backward_kernels")
    assert grids() == [fwd, fwd, bwd]


def test_the_cells_tiles_at_16384():
    """The benchmark cell's shape: 1024-row tiles (the rule's choice at
    T = 16,384, width 128, bfloat16), window 4,096: 70 of the 136 causal
    tiles are computed (66 skipped), 28 of them masked; the grid is 80
    steps a head where the plain causal kernel's is 256."""
    for kernel in ("fwd", "dq", "dkv", "bwd"):
        assert attention._flash_tiles(kernel, 16384, 16384, 128,
                                      jnp.bfloat16) == (1024, 1024)
    assert _tile_visits(16384, 1024, 1024, 4096) == {
        "steps": (80, 80), "computed": 70, "masked": 28}
    assert _tile_visits(16384, 1024, 1024, None) == {
        "steps": (256, 256), "computed": 136, "masked": 136}


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)
