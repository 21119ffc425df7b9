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
        assert visits == {"steps": (32, 32), "fetched": (26, 26),
                          "computed": 26, "masked": 19}


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
    dv's), ``fetched`` (the same pair: the steps whose inner block is
    another than the step before's, a row block's first among them; the
    rest start no copy), ``computed`` (tiles whose arithmetic runs) and
    ``masked`` (those of them that build a mask: only the tiles the
    diagonal or the window's edge crosses, with a window or none)."""
    n_q, n_k = t // bq, t // bk
    steps, fetched = [], []
    for rows, cols, n_rows, n_cols, queries in ((bq, bk, n_q, n_k, True),
                                                (bk, bq, n_k, n_q, False)):
        n_steps, block = attention._flash_inner(True, window, rows, cols,
                                                n_rows, n_cols, queries)
        walk = np.asarray(block(np.arange(n_rows)[:, None],
                                np.arange(n_steps)[None, :]))
        steps.append(n_rows * n_steps)
        fetched.append(n_rows + int((walk[:, 1:] != walk[:, :-1]).sum()))
    computed = masked = 0
    for i in range(n_q):
        first, last = attention._visible_blocks(
            i, bq, bk, n_k, *attention._window_reach(window, True))
        for j in range(first, last + 1):
            computed += 1
            masked += not attention._tile_is_full(i, j, bq, bk, window)
    return {"steps": tuple(steps), "fetched": tuple(fetched),
            "computed": computed, "masked": masked}


def _brute_tiles(t, bq, bk, window):
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    visible = (j <= i) if window is None else (j <= i) & (i - j < window)
    tiles = visible.reshape(t // bq, bq, t // bk, bk).transpose(0, 2, 1, 3)
    some, every = tiles.any((2, 3)), tiles.all((2, 3))
    return int(some.sum()), int((some & ~every).sum()), some


@pytest.mark.parametrize("t,bq,bk,window", [
    (1024, 128, 128, 300), (1024, 256, 128, 129), (1024, 128, 256, 256),
    (2048, 256, 256, 512), (2048, 512, 256, 2047), (1024, 128, 128, None),
    (1024, 256, 128, None), (8192, 1024, 1024, None),
    (8192, 512, 1024, None)])
def test_the_tiles_each_kernel_visits(t, bq, bk, window, request):
    """``_tile_visits`` (the kernels' own pieces) against a count made
    from the mask itself, with a window and with none (kanana-2's pair of
    tilings at 8,192 among them: its forward's, its one backward
    kernel's): the tiles computed are exactly those with a visible pair,
    those that build a mask exactly the ones not wholly visible, a step
    fetches only where it computes, and the grids the ``pallas_call``s are
    launched with are as long as the count says: the forward's and the
    one backward kernel's, which walks dk / dv's grid (PR 38), and the
    three of the two-kernel form."""
    computed, crossed, some = _brute_tiles(t, bq, bk, window)
    visits = _tile_visits(t, bq, bk, window)
    assert visits["computed"] == computed
    assert visits["masked"] == crossed
    assert visits["fetched"] == (computed, computed)
    n_q, n_k = t // bq, t // bk
    assert visits["steps"] == (n_q * int(some.sum(1).max()),
                               n_k * int(some.sum(0).max()))
    assert visits["steps"][0] < n_q * n_k or window is None \
        or window > t - bq
    q, k, v, g = _qkvg(t, h=1, d=8)
    fwd, bwd = _grids(q, k, v, g, bq, bk, window)
    assert fwd[1] * fwd[2] == visits["steps"][0]
    assert bwd[1] * bwd[2] == visits["steps"][1]
    request.getfixturevalue("two_backward_kernels")
    assert _grids(q, k, v, g, bq, bk, window) == [fwd, fwd, bwd]


def _launches(q, k, v, g, bq, bk, window):
    """The ``pallas_call``s of a gradient, in order."""
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: (attention.flash_attention(
        *a, True, bq, bk, window) * g).sum(), (0, 1, 2)))(q, k, v)
    return [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]


def _grids(*args):
    return [c.params["grid_mapping"].grid for c in _launches(*args)]


def test_the_cells_tiles_at_16384():
    """The benchmark cell's shape: 1024-row tiles (the rule's choice at
    T = 16,384, width 128, bfloat16), window 4,096: 70 of the 136 causal
    tiles are computed (66 skipped), 28 of them masked; the grid is 80
    steps a head where the plain causal kernel's is 256, of which 136
    compute and fetch and the 16 the diagonal crosses build a mask.
    kanana-2's cell (T = 8,192, ``tests/test_kanana2_kernels.py`` holds
    the tiles): the forward's 8 x 8 and the one backward kernel's 16 x 8."""
    for kernel in ("fwd", "dq", "dkv", "bwd"):
        assert attention._flash_tiles(kernel, 16384, 16384, 128,
                                      jnp.bfloat16) == (1024, 1024)
    assert _tile_visits(16384, 1024, 1024, 4096) == {
        "steps": (80, 80), "fetched": (70, 70), "computed": 70, "masked": 28}
    assert _tile_visits(16384, 1024, 1024, None) == {
        "steps": (256, 256), "fetched": (136, 136), "computed": 136,
        "masked": 16}
    assert _tile_visits(8192, 1024, 1024, None) == {
        "steps": (64, 64), "fetched": (36, 36), "computed": 36, "masked": 8}
    assert _tile_visits(8192, 512, 1024, None) == {
        "steps": (128, 128), "fetched": (72, 72), "computed": 72,
        "masked": 16}


@pytest.mark.parametrize("t,bq,bk,window", [
    (2048, 128, 128, None), (1024, 256, 128, None), (1024, 128, 256, None),
    (1024, 128, 128, 300)])
def test_a_step_that_computes_nothing_fetches_nothing(t, bq, bk, window,
                                                      request):
    """The index maps the launches are GIVEN (forward and the one
    backward kernel, then the two-kernel form's three), evaluated over a
    head's grid: every operand that moves along the inner axis (k, v in
    the forward and dq; q, g, lse, delta in dk / dv) changes its block
    within a row block exactly that row block's ``fetched`` - 1 times,
    and over the whole head never on a step that computes nothing
    (``_inner_block``'s second): Pallas starts a copy only where the
    block index differs from the step before's."""
    visits = _tile_visits(t, bq, bk, window)
    q, k, v, g = _qkvg(t, h=1, d=8)
    one = _launches(q, k, v, g, bq, bk, window)
    request.getfixturevalue("two_backward_kernels")
    two = _launches(q, k, v, g, bq, bk, window)
    assert (len(one), len(two)) == (2, 3)
    for calls in (one, two):
        for n, call in enumerate(calls):
            queries = n < len(calls) - 1        # the last walks key blocks
            rows, cols = (bq, bk) if queries else (bk, bq)
            mapping = call.params["grid_mapping"]
            _, n_rows, n_steps = mapping.grid
            i, s = np.meshgrid(np.arange(n_rows), np.arange(n_steps),
                               indexing="ij")
            computes = np.asarray(attention._inner_block(
                s, i, rows, cols, t // cols, True, window, queries)[1])
            assert int(computes.sum()) == visits["computed"]
            moving = 0
            for block in mapping.block_mappings[:mapping.num_inputs]:
                index = block.index_map_jaxpr
                at = np.asarray(jax.vmap(jax.vmap(
                    lambda i, s: jax.core.eval_jaxpr(
                        index.jaxpr, index.consts, jnp.int32(0), i, s)[1]))(
                    jnp.asarray(i, jnp.int32), jnp.asarray(s, jnp.int32)))
                if (at == at[:, :1]).all():
                    assert (at == i).all()
                    continue                    # the row block's own
                moving += 1
                changes = at[:, 1:] != at[:, :-1]
                assert n_rows + int(changes.sum()) == visits["fetched"][
                    0 if queries else 1]
                flat = at.reshape(-1)
                moved = flat[1:] != flat[:-1]
                assert not (moved & ~computes.reshape(-1)[1:]).any()
            assert moving == (2 if queries else 4), (n, moving)


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)
