"""The Pallas attention backward as ONE kernel (PR 38: the dk / dv kernel
accumulates dq as well, ``ops/attention.py`` ``_flash_backward``): in the
interpreter its five gradients EQUAL the two-kernel form's bit for bit
(plain causal, a window that crosses tiles, not causal with Tq != Tk, a
shared key part with a value width of its own; tiles square and not) and
are the reference's; ``_flash_tiles("bwd", ...)`` gives every preset
legal tiles inside the budget and none where a head's dq no longer fits,
where the backward takes two launches; and dq's output block only ever
moves onto a finished block, which the interpreter (it stores every
block at every step) cannot show. A file of its own beside
``tests/test_kanana2_kernels.py`` so that the two run on two workers.
CPU only, float32."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import pytest

attention = importlib.import_module("ray_tpu.ops.attention")

B, H = 2, 2


def _operands(tq, tk, d, dv, dr, seed=0, b=B):
    """[q, k, v, (q_shared, k_shared)], then the output's cotangent."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(b, tq, H, d), (b, tk, H, d), (b, tk, H, dv)]
    if dr:
        shapes += [(b, tq, H, dr), (b, tk, dr)]
    ops = [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]
    return ops, jax.random.normal(keys[5], (b, tq, H, dv), jnp.float32)


def _grad_fn(ops, g, causal, block_q, block_k, window):
    return jax.grad(lambda *a: (attention.flash_attention(
        *a[:3], causal, block_q, block_k, window, *a[3:]) * g).sum(),
        tuple(range(len(ops))))


def _reference(causal, window):
    """``dot_product_attention`` on ``_operands``' list: a shared key part
    joined to q and, repeated to the heads, to k."""
    def fn(q, k, v, q_shared=None, k_shared=None):
        if q_shared is not None:
            q = jnp.concatenate([q, q_shared], -1)
            k = jnp.concatenate([k, jnp.broadcast_to(
                k_shared[:, :, None],
                (*k.shape[:3], k_shared.shape[-1]))], -1)
        return attention.dot_product_attention(q, k, v, causal=causal,
                                               window=window)
    return fn


def _launches(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


# (name, Tq, Tk, d, dv, dr, causal, block_q, block_k, window)
FORMS = [
    ("causal", 512, 512, 32, 32, 0, True, 128, 128, None),
    ("causal, tall tiles", 512, 512, 32, 32, 0, True, 256, 128, None),
    ("causal, wide tiles", 512, 512, 32, 32, 0, True, 128, 256, None),
    ("window crossing tiles", 1024, 1024, 32, 32, 0, True, 128, 128, 300),
    ("window, tall tiles", 1024, 1024, 32, 32, 0, True, 256, 128, 129),
    ("window, wide tiles", 1024, 1024, 32, 32, 0, True, 128, 256, 256),
    ("not causal, Tq < Tk", 256, 512, 32, 32, 0, False, 128, 128, None),
    ("not causal, Tq > Tk", 512, 256, 32, 32, 0, False, 128, 256, None),
    ("causal, Tq > Tk", 512, 256, 32, 32, 0, True, 128, 128, None),
    ("causal, Tq < Tk", 256, 512, 32, 32, 0, True, 256, 128, None),
    ("shared key, dv != d", 512, 512, 32, 24, 16, True, 128, 128, None),
    ("shared key under a window", 1024, 1024, 32, 24, 16, True, 128, 256,
     129),
]


@pytest.mark.parametrize("form", FORMS, ids=[f[0] for f in FORMS])
def test_one_kernel_equals_two_bit_for_bit(form, request):
    """dq, dk, dv (and dq_shared, dk_shared) of the one-kernel backward
    equal the two-kernel form's on the same inputs, every bit; one launch
    fewer; and both are ``dot_product_attention``'s gradients."""
    _, tq, tk, d, dv, dr, causal, block_q, block_k, window = form
    ops, g = _operands(tq, tk, d, dv, dr)
    grad = _grad_fn(ops, g, causal, block_q, block_k, window)
    assert _launches(grad, *ops) == 2
    got = grad(*ops)
    request.getfixturevalue("two_backward_kernels")
    grad = _grad_fn(ops, g, causal, block_q, block_k, window)
    assert _launches(grad, *ops) == 3
    for name, a, b in zip(("dq", "dk", "dv", "dq_shared", "dk_shared"), got,
                          grad(*ops)):
        assert bool(jnp.array_equal(a, b)), name
        assert bool(jnp.any(a != 0)), name

    reference = _reference(causal, window)
    for name, a, b in zip(("dq", "dk", "dv", "dq_shared", "dk_shared"), got,
                          jax.grad(lambda *a: (reference(*a) * g).sum(),
                                   tuple(range(len(ops))))(*ops)):
        # ``test_flash_kernels_at_the_rules_own_tiles``' float32 tolerance
        assert float(jnp.abs(a - b).max()) <= 2e-4 * float(
            jnp.abs(b).max()), name


# (name, T, d, dv, dr, block_q, block_k): square tiles with skipped,
# crossed and full ones; kanana-2's backward's shape of tile and its
# transpose, with a shared key part as there
PLAIN = [
    ("square tiles", 1024, 16, 16, 0, 256, 256),
    ("shared key, tiles (512, 1024)", 2048, 8, 16, 8, 512, 1024),
    ("shared key, tiles (1024, 512)", 2048, 8, 16, 8, 1024, 512),
]


@pytest.mark.parametrize("backward", ["bwd", "dq+dkv"])
@pytest.mark.parametrize("form", PLAIN, ids=[f[0] for f in PLAIN])
def test_plain_causal_is_the_widest_window_bit_for_bit(form, backward,
                                                       request):
    """Plain causal attention walks its grid by the windowed kernels' rule
    (PR 50): the output and dq, dk, dv (and dq_shared, dk_shared) with no
    window EQUAL the same call with ``window = T``, which takes the
    windowed path whatever the window holds, every bit, in the one-kernel
    backward and in the two-kernel form; and are the masked reference's."""
    _, t, d, dv, dr, block_q, block_k = form
    if backward == "dq+dkv":
        request.getfixturevalue("two_backward_kernels")
    ops, g = _operands(t, t, d, dv, dr, seed=4, b=1)

    def run(window):
        out, vjp = jax.vjp(lambda *a: attention.flash_attention(
            *a[:3], True, block_q, block_k, window, *a[3:]), *ops)
        return (out, *vjp(g))

    got = run(None)
    out, vjp = jax.vjp(_reference(True, None), *ops)
    names = ("out", "dq", "dk", "dv", "dq_shared", "dk_shared")
    for name, a, b, c in zip(names, got, run(t), (out, *vjp(g))):
        assert bool(jnp.array_equal(a, b)), name
        assert float(jnp.abs(a - c).max()) <= 2e-4 * float(
            jnp.abs(c).max()), name


# -- the tiles, and where a head's dq stops fitting ----------------------------------

# (T, d, dv, dr) of every preset whose rows reach the kernels (above 1024
# keys): `moe_small`, `llama2_7b`, `olmoe_1b_7b`, `llama3_8b`,
# `smallthinker_21b_a3b`, `mistral_7b` / `mixtral_8x7b` / `qwen2_7b` at
# their 32,768, GPT-2's width at 2,048 (`chip_smoke.py`) and at 4,096, and
# kanana-2's latent form at its cell's 8,192 rows and at half of its 32,768.
PRESETS = [(2048, 64, 64, 0), (4096, 128, 128, 0), (8192, 128, 128, 0),
           (16384, 128, 128, 0), (32768, 128, 128, 0), (4096, 64, 64, 0),
           (8192, 128, 128, 64), (16384, 128, 128, 64)]


@pytest.mark.parametrize("t,d,dv,dr", PRESETS)
def test_bwd_tiles_are_legal_and_inside_the_budget(t, d, dv, dr):
    bq, bk = attention._flash_tiles("bwd", t, t, d, jnp.bfloat16, dv, dr)
    assert bq % 128 == 0 and bk % 128 == 0 and t % bq == 0 and t % bk == 0
    assert max(bq, bk) <= attention._FLASH_ROWS
    q = jax.ShapeDtypeStruct((2, t, 4, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, t, 4, dv), jnp.bfloat16)
    qs = jax.ShapeDtypeStruct((2, t, 4, dr), jnp.bfloat16) if dr else None
    launch_q, launch_k, params = attention._flash_launch(
        "bwd", q, q, None, None, v, qs)
    assert (launch_q, launch_k) == (bq, bk)
    need = attention._flash_vmem_bytes("bwd", bq, bk, d, jnp.bfloat16, dv,
                                       dr, t)
    assert need <= params.vmem_limit_bytes <= attention._FLASH_VMEM_MOST
    # the whole head's float32 dq is in the account, beside the dk / dv
    # kernel's own tiles and dq's two output blocks
    rows = attention._vmem_tile
    assert need == attention._flash_vmem_bytes(
        "dkv", bq, bk, d, jnp.bfloat16, dv, dr) + rows(t, d, 4) + (
        rows(t, dr, 4) if dr else 0) + 2 * (
        rows(bq, d, 2) + (rows(bq, dr, 2) if dr else 0))


@pytest.mark.parametrize("t,d,dr", [(65536, 128, 0), (32768, 128, 64),
                                    (131072, 64, 0)])
def test_past_the_accumulators_bound_the_backward_takes_two_launches(t, d,
                                                                    dr):
    """A head's dq (T x d x 4 bytes, the shared part's beside it) leaves
    no room for the smallest tiles: no "bwd" tiles, "dq" and "dkv" still
    have theirs, and the backward rule launches both (counted in the
    jaxpr, not run)."""
    assert attention._flash_tiles("bwd", t, t, d, jnp.bfloat16, d, dr) is None
    assert attention._flash_vmem_bytes(
        "bwd", 128, 128, d, jnp.bfloat16, d, dr, t) > attention._FLASH_VMEM_MOST
    for kernel in ("dq", "dkv"):
        assert attention._flash_tiles(kernel, t, t, d, jnp.bfloat16, d, dr)
    shapes = [(1, t, 1, d)] * 3 + ([(1, t, 1, dr), (1, t, dr)] if dr else [])
    ops = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]
    grad = jax.grad(lambda *a: attention.flash_attention(
        *a[:3], True, None, None, None, *a[3:]).astype(jnp.float32).sum(),
        tuple(range(len(ops))))
    assert _launches(grad, *ops) == 3
    half = [jax.ShapeDtypeStruct((s[0], t // 8, *s[2:]), jnp.bfloat16)
            for s in shapes]
    assert _launches(grad, *half) == 2


# -- dq's output block only moves onto finished blocks -------------------------------

def _walk(tq, tk, bq, bk, causal, window):
    """The "bwd" grid of one head in order: (key block, query block, the
    tile is computed, dq's output block index)."""
    n_q, n_k = tq // bq, tk // bk
    n_steps, q_of = attention._flash_inner(causal, window, bk, bq, n_k, n_q,
                                           False)
    for j in range(n_k):
        for step in range(n_steps):
            i, in_range = attention._inner_block(step, j, bk, bq, n_q, causal,
                                                 window, False)
            out = int(attention._dq_out_block(int(q_of(j, step)), j, bq, bk,
                                              n_q, n_k, causal))
            yield j, int(i), bool(in_range), out


@pytest.mark.parametrize("tq,tk,bq,bk,causal,window", [
    (f[1], f[2], f[7], f[8], f[6], f[9]) for f in FORMS] + [
    (16384, 16384, 1024, 1024, True, None), (16384, 16384, 1024, 1024, True,
                                             4096),
    (8192, 8192, 512, 1024, True, None), (8192, 8192, 1024, 512, True, None),
    (2048, 8192, 1024, 1024, False, None), (4096, 4096, 512, 1024, True,
                                            1500)])
def test_dq_leaves_once_and_finished(tq, tk, bq, bk, causal, window):
    """What the compiled kernel does and the interpreter does not: an
    output block is written back when its index moves on (and at the
    grid's end), holding whatever the kernel last stored in it. Every
    query block's dq is written back exactly once, after the tile that
    finishes it (``_dq_last_key_block``) stored it during that stay, and
    every tile that adds to it came before."""
    n_q, n_k = tq // bq, tk // bk
    written_back, stored_this_stay, added_after_store = [], False, set()
    stored, at = set(), None
    for j, i, computed, out in _walk(tq, tk, bq, bk, causal, window):
        if at is not None and out != at:
            assert stored_this_stay, (at, j, i)
            written_back.append(at)
            stored_this_stay = False
        at = out
        if not computed:
            continue
        if i in stored:
            added_after_store.add(i)
        if j == int(attention._dq_last_key_block(i, bq, bk, n_k, causal)):
            assert out == i, (j, i, out)
            stored.add(i)
            stored_this_stay = True
    assert stored_this_stay
    written_back.append(at)
    assert written_back == list(range(n_q))
    assert not added_after_store
    if causal and bq == bk and tq == tk:        # the key block's own
        assert all(out == j for j, *_, out in _walk(tq, tk, bq, bk, causal,
                                                    window))
