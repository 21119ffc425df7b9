"""Correctness of ops/ kernels and parallel/ strategies on the virtual
8-device CPU mesh (test strategy per SURVEY.md §4 "lesson")."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (
    attention,
    blockwise_attention,
    dot_product_attention,
    flash_attention,
    ring_attention_sharded,
)
from ray_tpu.parallel import (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_SEQUENCE,
    AXIS_TENSOR,
    MeshConfig,
    fsdp_spec_for,
    infer_param_specs,
    pipelined_apply,
    shard_params,
)
from jax.sharding import PartitionSpec as P

# ``ray_tpu.ops.attention`` the attribute is the dispatch function.
attention_mod = importlib.import_module("ray_tpu.ops.attention")


def _qkv(b=2, t=128, h=4, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def test_blockwise_matches_reference():
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=True)
    blk = blockwise_attention(q, k, v, causal=True, block_k=32)
    np.testing.assert_allclose(ref, blk, atol=2e-5, rtol=2e-5)


def test_blockwise_noncausal_with_padding():
    q, k, v = _qkv(t=100)  # 100 % 32 != 0 → exercises the pad path
    ref = dot_product_attention(q, k, v, causal=False)
    blk = blockwise_attention(q, k, v, causal=False, block_k=32)
    np.testing.assert_allclose(ref, blk, atol=2e-5, rtol=2e-5)


def test_flash_kernel_matches_reference():
    q, k, v = _qkv(t=128)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, 64, 64)
    np.testing.assert_allclose(ref, out, atol=2e-5, rtol=2e-5)


def test_flash_gradients_match_reference():
    q, k, v = _qkv(b=1, t=64, h=2, d=16)

    def loss_ref(q, k, v):
        return dot_product_attention(q, k, v, causal=True).sum()

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, True, 32, 32).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_kernels_multiblock(causal):
    """The Pallas backward (dq pass + dk/dv pass, probabilities rebuilt
    from the saved logsumexp) matches reference gradients with a
    NON-TRIVIAL cotangent across multiple q/k blocks."""
    q, k, v = _qkv(b=2, t=128, h=2, d=32)
    w = jnp.asarray(np.random.RandomState(7).randn(32), jnp.float32)

    def loss(att):
        def f(q, k, v):
            out = att(q, k, v)
            return (jnp.tanh(out @ w) * jnp.cos(out.sum(-1))).sum()
        return f

    ref = loss(lambda q, k, v: dot_product_attention(q, k, v,
                                                     causal=causal))
    fla = loss(lambda q, k, v: flash_attention(q, k, v, causal, 32, 64))
    g_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(fla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


# Largest |difference| allowed, as a share of the reference's largest
# entry: float32 as the explicit-tile tests above; bfloat16 two units in
# the last place (PR 25's rule for the blocked path), with operands of
# every matmul of all three kernels in bfloat16.
_FLASH_TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2.0 ** -6}


@pytest.mark.parametrize("backward", ["bwd", "dq+dkv"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [64, 128])
def test_flash_kernels_at_the_rules_own_tiles(d, dtype, backward, request):
    """Forward, dq, dk and dv at the tiles each kernel picks for itself
    (no ``block_q`` / ``block_k``), T = 2048, causal, against
    ``dot_product_attention`` in the same dtype; the backward as the one
    kernel the shapes give it (PR 38) and as the two it takes where a
    head's dq does not fit. The cotangent is the
    multiblock test's loss's, taken ONCE (at the float32 reference's
    output) and handed to both sides: that loss's ``cos(out.sum(-1))``
    turns one bfloat16 rounding of ``out`` into percents of cotangent,
    which is the loss's doing and no kernel's."""
    t = 2048
    for kernel in ("fwd", "dq", "dkv", "bwd"):
        bq, bk = attention_mod._flash_tiles(kernel, t, t, d, dtype)
        assert t // bq > 1 or t // bk > 1, (kernel, bq, bk)
    if backward == "dq+dkv":
        request.getfixturevalue("two_backward_kernels")
    q, k, v = _qkv(b=1, t=t, h=1, d=d, seed=3)
    w = jnp.asarray(np.random.RandomState(7).randn(d), jnp.float32)
    g = jax.grad(lambda o: (jnp.tanh(o @ w) * jnp.cos(o.sum(-1))).sum())(
        dot_product_attention(q, k, v, causal=True))
    q, k, v, g = (x.astype(dtype) for x in (q, k, v, g))

    def run(att):
        out, vjp = jax.vjp(att, q, k, v)
        return (out, *vjp(g))

    want = run(lambda q, k, v: dot_product_attention(q, k, v, causal=True))
    got = run(lambda q, k, v: flash_attention(q, k, v, True))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= _FLASH_TOL[dtype] * np.abs(b).max(), \
            name


# (T, head width) of every preset above 1024 (`moe_small`, `llama2_7b`,
# `olmoe_1b_7b`, `llama3_8b`, `mistral_7b` / `mixtral_8x7b` / `qwen2_7b`),
# GPT-2's width at their lengths, and lengths 1024-tiles do not divide;
# then Tq != Tk, and a float32 head no preset has, where the tiles step
# down to fit.
@pytest.mark.parametrize("tq,tk,d,dtype", [
    (t, t, d, dtype)
    for t in (1280, 1536, 2048, 3072, 4096, 8192, 32768)
    for d in (64, 128) for dtype in (jnp.bfloat16, jnp.float32)
] + [(128, 4096, 128, jnp.bfloat16), (4096, 128, 128, jnp.bfloat16),
     (4096, 4096, 512, jnp.float32)])
def test_flash_tile_rule_gives_legal_tiles(tq, tk, d, dtype):
    """Whole 128-row tiles that divide the lengths, in a grid step whose
    VMEM, by the kernel's own arithmetic, is inside what its
    ``pallas_call`` is given, which is inside the rule's budget."""
    q = jax.ShapeDtypeStruct((2, tq, 4, d), dtype)
    k = jax.ShapeDtypeStruct((2, tk, 4, d), dtype)
    for kernel in ("fwd", "dq", "dkv"):
        bq, bk = attention_mod._flash_tiles(kernel, tq, tk, d, dtype)
        assert bq % 128 == 0 and bk % 128 == 0 and bq > 0 and bk > 0
        assert tq % bq == 0 and tk % bk == 0
        assert max(bq, bk) <= attention_mod._FLASH_ROWS
        launch_q, launch_k, params = attention_mod._flash_launch(
            kernel, q, k, None, None)
        assert (launch_q, launch_k) == (bq, bk)
        need = attention_mod._flash_vmem_bytes(kernel, bq, bk, d, dtype)
        assert need <= params.vmem_limit_bytes \
            <= attention_mod._FLASH_VMEM_MOST, (kernel, bq, bk, need)
    # the one backward kernel: tiles that hold the head's dq as well, or
    # none where that leaves no room for the smallest ones
    least = attention_mod._flash_vmem_bytes("bwd", 128, 128, d, dtype, tq=tq)
    tiles = attention_mod._flash_tiles("bwd", tq, tk, d, dtype)
    assert (tiles is None) == (least > attention_mod._FLASH_VMEM_MOST)
    if tiles:
        bq, bk = tiles
        assert bq % 128 == 0 and bk % 128 == 0 and tq % bq == 0 == tk % bk
        assert attention_mod._flash_launch("bwd", q, k, None, None)[:2] == tiles
        assert least <= attention_mod._flash_vmem_bytes(
            "bwd", bq, bk, d, dtype, tq=tq) <= attention_mod._FLASH_VMEM_MOST


@pytest.mark.parametrize("tq,tk", [(2000, 2000), (1100, 2048), (2048, 1100),
                                   (64, 2048)])
def test_flash_tile_rule_has_no_tile_for_ragged_lengths(tq, tk):
    for kernel in ("fwd", "dq", "dkv", "bwd"):
        assert attention_mod._flash_tiles(
            kernel, tq, tk, 128, jnp.bfloat16) is None
    q = jnp.zeros((1, tq, 1, 128))
    kv = jnp.zeros((1, tk, 1, 128))
    with pytest.raises(ValueError, match="whole 128-row tiles"):
        flash_attention(q, kv, kv, True)


def _gqa_case(t, d, dtype, *, h=4, kv_heads=2, seed=0):
    """q, narrow k / v and a cotangent, as the llama block has them
    before ``_expand_gqa`` repeats k and v over the query heads."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = [(1, t, h, d), (1, t, kv_heads, d), (1, t, kv_heads, d),
              (1, t, h, d)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(ks, shapes)]


def _out_and_grads(fn, q, k, v, w):
    def loss(q, k, v):
        rep = q.shape[2] // k.shape[2]
        o = fn(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2))
        return (o.astype(jnp.float32) * w.astype(jnp.float32)).sum(), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (o, *grads)


# Largest |difference| allowed, as a share of the reference's largest
# entry: float32 differs by the order of a row's sums only; bfloat16 by
# that and by dk / dv arriving as a bfloat16 sum of prefix-shaped pieces
# (two units in the last place).
_BLOCKED_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -6}


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t", [256, 512, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_auto_blocked_causal_matches_reference(dtype, t, d):
    q, k, v, w = _gqa_case(t, d, dtype)
    want = _out_and_grads(dot_product_attention, q, k, v, w)
    got = _out_and_grads(
        lambda q, k, v: attention(q, k, v, causal=True, impl="auto"),
        q, k, v, w)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= _BLOCKED_TOL[dtype] * np.abs(b).max(), \
            name


@pytest.mark.parametrize("block_q", [32, 64, 128])
def test_blocked_causal_any_block_count(block_q):
    q, k, v, w = _gqa_case(256, 32, jnp.float32, seed=1)
    want = _out_and_grads(dot_product_attention, q, k, v, w)
    got = _out_and_grads(
        lambda q, k, v: attention_mod.causal_blocked_attention(
            q, k, v, block_q=block_q), q, k, v, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="divide into blocks"):
        attention_mod.causal_blocked_attention(q, k, v, block_q=96)


def _record_paths(monkeypatch) -> list:
    """Replaces the four implementations ``attention`` picks from by
    recorders of (which, the query tile it was handed, if any)."""
    taken = []

    def recorder(name):
        def fn(q, k, v, *a, **kw):
            taken.append((name, kw.get("block_q", a[1] if a[1:] else None)))
            return q
        return fn

    for name, attr in [("blocked", "causal_blocked_attention"),
                       ("plain", "dot_product_attention"),
                       ("blockwise", "blockwise_attention"),
                       ("flash", "flash_attention")]:
        monkeypatch.setattr(attention_mod, attr, recorder(name))
    return taken


@pytest.mark.parametrize("impl,causal,tq,tk,want", [
    ("auto", True, 256, 256, ("blocked", 128)),
    ("auto", True, 512, 512, ("blocked", 128)),
    ("auto", True, 768, 768, ("blocked", 128)),
    ("auto", True, 1024, 1024, ("blocked", 256)),
    ("auto", False, 512, 512, ("plain", None)),      # nothing is masked
    ("auto", True, 128, 512, ("plain", None)),       # Tq != Tk
    ("auto", True, 128, 128, ("plain", None)),       # one block: the presets
    ("auto", True, 300, 300, ("plain", None)),       # not whole 128-row tiles
    ("auto", True, 2048, 2048, ("blockwise", None)),  # above 1024: as before
    ("reference", True, 512, 512, ("plain", None)),
])
def test_which_path_attention_takes(monkeypatch, impl, causal, tq, tk, want):
    """The ``auto`` rule reads shapes alone; a caller sets nothing."""
    taken = _record_paths(monkeypatch)
    q = jnp.zeros((1, tq, 2, 8))
    kv = jnp.zeros((1, tk, 2, 8))
    attention(q, kv, kv, causal=causal, impl=impl)
    assert taken == [want]


@pytest.mark.parametrize("tq,tk,want", [
    (2048, 2048, ("flash", None)),
    (4096, 4096, ("flash", None)),
    (1280, 1280, ("flash", None)),      # 640-row tiles
    (128, 2048, ("flash", None)),       # Tq != Tk, both whole tiles
    (2000, 2000, ("blockwise", None)),  # no whole 128-row tile divides it
    (100, 2048, ("blockwise", None)),
    (1024, 1024, ("blocked", 256)),     # the threshold has not moved
])
def test_which_path_auto_takes_on_a_tpu(monkeypatch, tq, tk, want):
    """Above 1024 keys, on a TPU, the kernel where the rule has tiles
    for both lengths and ``blockwise_attention`` where it has none; the
    caller hands the kernel no tile."""
    import types

    taken = _record_paths(monkeypatch)
    q = jnp.zeros((1, tq, 2, 64), jnp.bfloat16)
    kv = jnp.zeros((1, tk, 2, 64), jnp.bfloat16)
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])
    attention(q, kv, kv, causal=True, impl="auto")
    assert taken == [want]


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = MeshConfig(data=1, sequence=8).build()
    q, k, v = _qkv(b=2, t=128, h=2, d=16)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(ref, np.asarray(out), atol=2e-5, rtol=2e-5)


def test_ring_attention_grads_flow():
    mesh = MeshConfig(data=1, sequence=8).build()
    q, k, v = _qkv(b=1, t=64, h=2, d=16)

    def loss(q, k, v):
        return ring_attention_sharded(q, k, v, mesh).sum()

    def loss_ref(q, k, v):
        return dot_product_attention(q, k, v).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), b, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# parallel/
# ---------------------------------------------------------------------------


def test_mesh_config_wildcard_and_order():
    mesh = MeshConfig(tensor=2).build()  # data absorbs 4
    assert mesh.shape[AXIS_DATA] == 4 and mesh.shape[AXIS_TENSOR] == 2
    assert mesh.axis_names == (AXIS_DATA, AXIS_TENSOR)
    with pytest.raises(ValueError):
        MeshConfig(data=3, tensor=5).build()


def test_fsdp_spec_inference():
    assert fsdp_spec_for((128, 64), 8) == P(AXIS_FSDP, None)
    # base TP spec on dim 0 → fsdp takes dim 1
    assert fsdp_spec_for((128, 64), 8, P(AXIS_TENSOR, None)) == P(AXIS_TENSOR, AXIS_FSDP)
    # nothing divisible → untouched
    assert fsdp_spec_for((7, 5), 8) == P(None, None)


def test_shard_params_places_on_mesh():
    mesh = MeshConfig(data=1, fsdp=8).build()
    params = {"w": jnp.ones((64, 16)), "b": jnp.ones((3,))}
    placed, shardings = shard_params(params, mesh)
    specs = infer_param_specs(params, mesh)
    assert specs["w"] == P(AXIS_FSDP, None)
    assert specs["b"] == P(None)
    assert placed["w"].sharding.is_equivalent_to(shardings["w"], 2)


def test_spmd_pipeline_matches_sequential():
    """4-stage linear pipeline == sequential composition of the stages."""
    mesh = MeshConfig(data=1, pipeline=4).build(jax.devices()[:4])
    key = jax.random.PRNGKey(1)
    dim = 8
    params = [
        {"w": jax.random.normal(k, (dim, dim)) / np.sqrt(dim)}
        for k in jax.random.split(key, 4)
    ]
    batch = jax.random.normal(jax.random.PRNGKey(2), (16, dim))

    def stage(p, x):
        return jnp.tanh(x @ p["w"])

    expected = batch
    for p in params:
        expected = stage(p, expected)

    out = pipelined_apply(stage, params, mesh, batch, num_microbatches=8)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5, rtol=1e-5)
