"""The delta rule's Pallas kernels with ONE log-decay a head and fewer key
heads than value heads (Gated DeltaNet; ``ray_tpu/ops/linear_attention.py``
``_head_matrices``, ``_head_matrices_grad``, ``_load_heads``), in the
interpreter on the CPU at 128-wide heads: they equal the XLA scan, forward
and all five gradients, and the per-CHANNEL kernels fed the same decay on
every lane and the key heads repeated; a ``T`` that is no whole number of
chunks; a decay at the strong end; bfloat16 operands. And compiled for a
described ``v5e:2x2`` device at the cell's widths: ``_on_one_tpu`` takes
this case, the mixer's forward, recompute and backward hold the kernels
(the rule's, the chains' and the head norm's) and no ``while`` (the scan's
loop), and no array of the operands' size changes its tiling on the way
(``tests/test_kda_layout.py``'s assertion).

Nothing here is a speed. The interpreter compiles the two kernels once a
shape; the cases share them through module-scoped fixtures.
"""

from __future__ import annotations

import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import mixers, transformer
from ray_tpu.ops import linear_attention as la

F32 = jnp.float32
T, HV, HK, D = 150, 4, 2, 128       # T: two chunks and 22 positions
NAMES = ("q", "k", "v", "g", "beta")


def operands(seed: int = 0, *, decay: float = 0.5, t: int = T,
             dtype=F32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = la.l2_norm(jax.random.normal(ks[0], (1, t, HK, D)))
    k = la.l2_norm(jax.random.normal(ks[1], (1, t, HK, D)))
    v = jax.random.normal(ks[2], (1, t, HV, D))
    g = -decay * jax.random.uniform(ks[3], (1, t, HV))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, HV)))
    weight = jax.random.normal(ks[5], (1, t, HV, D))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), weight


def _both(rule, ops, weight):
    """(sum(o * weight), the five gradients) under ``highest``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda *a: (rule(*a).astype(F32) * weight).sum(),
            argnums=range(5)))(*ops)


def by_channel(q, k, v, g, beta):
    """The per-channel kernels on the same rule: the decay on every lane
    of its head, a key head repeated to its value heads."""
    each = HV // HK
    return la._by_kernels(jnp.repeat(q, each, 2), jnp.repeat(k, each, 2), v,
                          jnp.repeat(g[..., None], D, -1), beta)


@pytest.fixture(scope="module")
def results():
    ops, weight = operands()
    return {"scan": _both(la._by_scan, ops, weight),
            "head": _both(la._by_kernels, ops, weight),
            "channel": _both(by_channel, ops, weight)}


def _near(a, b, tol: float) -> bool:
    return float(jnp.abs(a - b).max()) <= tol * (1.0 + float(jnp.abs(b).max()))


@pytest.mark.parametrize("other", ["scan", "channel"])
def test_the_forward_is_the_scans_and_the_per_channel_kernels(results, other):
    assert _near(results["head"][0], results[other][0], 2e-6)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("other", ["scan", "channel"])
def test_a_gradient_is_the_scans_and_the_per_channel_kernels(results, other,
                                                             name):
    i = NAMES.index(name)
    mine, theirs = results["head"][1][i], results[other][1][i]
    # comes back as it went: q and k by KEY head, g and beta a head
    assert mine.shape == theirs.shape == operands()[0][i].shape
    assert _near(mine, theirs, 1e-5), name


def test_the_recurrence_token_by_token():
    """Against the definition itself, not another chunked form."""
    (q, k, v, g, beta), _ = operands(1, t=96)
    each = HV // HK
    qr, kr = jnp.repeat(q, each, 2), jnp.repeat(k, each, 2)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        seen = jnp.einsum("bhc,bhce->bhe", k_t, state)
        state = state + jnp.einsum("bhc,bhe->bhce", k_t,
                                   b_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhc,bhce->bhe", q_t, state) / D ** 0.5

    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(token, jnp.zeros((1, HV, D, D), F32), jax.tree.map(
            lambda a: jnp.moveaxis(a, 1, 0), (qr, kr, v, g, beta)))
        got = la._by_kernels(q, k, v, g, beta)
    assert _near(got, jnp.moveaxis(o, 0, 1), 2e-6)


@pytest.mark.parametrize("decay", [8.0, 0.0])
def test_the_strong_end_of_the_decay_and_none_stay_finite_and_the_scans(
        decay):
    """|g| of 8 a token is 512 over a chunk: every exponent is a
    difference with the later position first, so nothing overflows (a
    running sum of 512 is float32 to 6e-5, which is the tolerance's)."""
    ops, weight = operands(2, decay=decay, t=128)
    (o_k, g_k), (o_s, g_s) = (_both(la._by_kernels, ops, weight),
                              _both(la._by_scan, ops, weight))
    assert _near(o_k, o_s, 2e-5)
    for a, b in zip(g_k, g_s):
        assert bool(jnp.isfinite(a).all()) and _near(a, b, 2e-5)


def test_bfloat16_operands_are_as_near_the_scan_as_float32s_rounding():
    ops, weight = operands(3, t=128, dtype=jnp.bfloat16)
    (o_k, g_k), (o_s, g_s) = (_both(la._by_kernels, ops, weight),
                              _both(la._by_scan, ops, weight))
    assert _near(o_k, o_s, 2e-2)
    for a, b in zip(g_k, g_s):
        assert a.dtype == b.dtype
        assert _near(a.astype(F32), b.astype(F32), 3e-2)


def test_a_key_head_for_more_value_heads_than_a_step_holds_is_repeated():
    """8 value heads on ONE key head: no step of four holds whole key
    heads, so q and k are repeated ahead of the kernels; same answer."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    t, hv = 64, 8
    q = la.l2_norm(jax.random.normal(ks[0], (1, t, 1, D)))
    k = la.l2_norm(jax.random.normal(ks[1], (1, t, 1, D)))
    v = jax.random.normal(ks[2], (1, t, hv, D))
    g = -0.3 * jax.random.uniform(ks[3], (1, t, hv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, hv)))
    with jax.default_matmul_precision("highest"):
        assert _near(la._by_kernels(q, k, v, g, beta),
                     la._by_scan(q, k, v, g, beta), 2e-6)


def test_the_public_rule_takes_the_scan_on_the_cpu_and_the_same_shapes():
    ops, weight = operands(5, t=70)
    with jax.default_matmul_precision("highest"):
        o = la.gated_delta_rule(*ops)
        assert o.shape == weight.shape
        assert _near(o, la._by_scan(*ops), 0.0)
        flat_v = ops[2].reshape(1, 70, -1)
        assert _near(la.gated_delta_rule(ops[0], ops[1], flat_v, *ops[3:]),
                     o, 0.0)


def test_the_counter_reads_a_decay_a_head():
    g = operands(6)[0][3]
    by_hand = min(float(g[0, s:s + la.CHUNK].sum(0).min())
                  for s in range(0, T, la.CHUNK))
    assert float(la.log_decay_min(g)) == pytest.approx(by_hand, rel=1e-6)


# -- compiled for a described v5e, the cell's widths ------------------------------

from test_kda_layout import chip  # noqa: E402,F401  (the fixture)

# 3,072 positions: no activation is then the size of a weight (at 1,024 a
# [2048, 16, 128] projection has as many values as a [1, 1024, 32 x 128] row)
B, TC = 1, 3072


def _mixer_hlo(device):
    c = transformer.qwen3_next_80b_a3b(n_layers=4)
    one = jax.sharding.SingleDeviceSharding(device)
    w = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype, sharding=one),
        c.shapes()["layers"]["gdn"])
    assert w["wq"].shape == (2048, 16, 128) and w["wv"].shape == (2048, 32, 128)
    h = jax.ShapeDtypeStruct((B, TC, c.d_model), c.compute_dtype, sharding=one)

    @jax.checkpoint
    def layer(h, w):
        o, counters = mixers._gdn_mixer(h, w, c)
        return jnp.square(o.astype(F32)).sum() + counters["log_decay_min"]

    with mock.patch.object(jax, "devices", lambda *a, **k: [device]):
        assert la._on_one_tpu(h, c.kda_head_dim, c.kda_head_dim)
        lowered = jax.jit(jax.value_and_grad(layer, argnums=(0, 1))).lower(
            h, w)
    return lowered.compile().as_text()


_BIG = re.compile(r"= (?:f32|bf16)\[([\d,]+)\]\S* (copy|transpose|reshape)\(")


def test_on_a_described_v5e_the_mixer_runs_the_kernels_and_no_scan(chip):  # noqa: F811
    import math

    hlo = _mixer_hlo(chip)
    calls = [ln for ln in hlo.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    # the rule forward, recomputed, backward; a chain a call and pass
    assert sum("attn_core" in ln for ln in calls) == 3
    assert sum("kda_conv" in ln for ln in calls) == 9
    # ``silu_gated_head_norm``: forward, recomputed, backward
    assert sum("kda_gate" in ln for ln in calls) == 3
    assert " while(" not in hlo             # no scan of the delta rule
    # q, k (16 heads), v, z, o (32 heads): none crosses between tilings
    sizes = {B * TC * 16 * 128, B * TC * 32 * 128}
    moved = [ln.strip()[:300] for ln in hlo.splitlines()
             if (m := _BIG.search(ln))
             and math.prod(map(int, m.group(1).split(","))) in sizes]
    assert moved == []
