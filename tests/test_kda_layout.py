"""The layout in which a KDA layer hands arrays from its projections to the
delta rule and on to its head norm (``ray_tpu/ops/linear_attention.py``
``conv_silu``, ``gates``, ``gated_delta_rule``, ``gated_head_norm``,
``log_decay_min``; ``ray_tpu/models/mixers.py`` ``_kda_mixer``): FLAT,
[B, T, H * d], a head a 128-lane slice, which is how the rule's Pallas
kernels read q, k, v, ``g`` and write ``o``. On the CPU at tiny widths: the
flat ``g``, the head norm and their gradients are the parent's
``btr,rhk->bthk`` forms' (kept here, ``_gates_by_heads``,
``_head_norm_by_heads``), and the rule and the counter give the same for
rank-3 and rank-4 operands; at 128-wide heads the head norm's Pallas
kernels (``_head_norm_kernels``, interpreted, both gates) are the plain form
``_head_norm_plain``, value and every gradient, and ``_on_one_tpu`` alone
chooses between them. Compiled for a described ``v5e:2x2`` device at
the cell's widths: the mixer's forward, recompute and backward hold NO
relayout of an array of that size, float32 or bfloat16, the convolution
chains are Pallas calls under ``kda_conv`` and the head norm three under
``kda_gate``, where no float32 array of that size is written but ``g``'s
(PR 54); the relayout assertion fails on each
of the parent's forms (PR 43's ``gates`` and ``gated_head_norm`` by heads,
PR 49's projections and ``conv_silu`` by heads: ``_mixer_by_heads``), so it
sees the fault (268 MB float32 or 134 MB bfloat16 crossing between two
tilings: PERF.md section 6, PR 40, PR 43 and PR 49). The same described
device compiles the state-space scan's two kernels
(``ray_tpu/ops/state_space.py``) at Nemotron-3-Nano's widths (PR 59), and
``_ssm_mixer`` whole (PR 61): the chain with a bias row and the gated group
norm over 512-lane groups are Pallas calls, three each beside the scan's
three, and no array of ``y``'s or ``[x | B | C]``'s size is relaid or written
in float32 under their scopes, which the parent's plain group norm fails.

Nothing here is a speed. The topology is described inside a module-scoped
fixture, never at import (the on-chip-measurement guide).
"""

from __future__ import annotations

import functools
import math
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import mixers, transformer
from ray_tpu.ops import linear_attention as la

F32 = jnp.float32


def _gates_by_heads(h, w):
    """``gates`` as the parent made it: ``g`` [B, T, H, dk] from the
    einsum ``btr,rhk->bthk``, 8 HEADS in a tile's sublanes on the chip."""
    dt = h.dtype
    with jax.named_scope("kda_gate"):
        low = jnp.einsum("btd,dr->btr", h, w["f_a"].astype(dt))
        f = jnp.einsum("btr,rhk->bthk", low, w["f_b"].astype(dt),
                       preferred_element_type=F32)
        g = -jnp.exp(w["A_log"].astype(F32))[:, None] * \
            jax.nn.softplus(f + w["dt_bias"].astype(F32))
        beta = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bth", h, w["w_beta"].astype(dt),
            preferred_element_type=F32))
        return g, beta


def _head_norm_by_heads(o, h, w, *, eps: float):
    """``gated_head_norm`` as the parent made it: the gate by the einsum
    ``btr,rhk->bthk``, the mean over the last dimension of ``o`` [B, T, H,
    dv], whose flat form the kernels write."""
    dt = h.dtype
    with jax.named_scope("kda_gate"):
        low = jnp.einsum("btd,dr->btr", h, w["g_a"].astype(dt))
        gate = jnp.einsum("btr,rhk->bthk", low, w["g_b"].astype(dt),
                          preferred_element_type=F32)
        of = o.astype(F32)
        normed = of * jax.lax.rsqrt(
            jnp.mean(of * of, -1, keepdims=True) + eps)
        return (normed * w["o_norm"].astype(F32)
                * jax.nn.sigmoid(gate)).astype(dt)


def _conv_silu_by_heads(q, k, v, w_q, w_k, w_v):
    """``conv_silu`` as the parent made it: operands [B, T, H, d], three
    float32 chains in plain XLA."""
    with jax.named_scope("kda_conv"):
        return (la.l2_norm(jax.nn.silu(la._conv(q, w_q))).astype(q.dtype),
                la.l2_norm(jax.nn.silu(la._conv(k, w_k))).astype(k.dtype),
                jax.nn.silu(la._conv(v, w_v)).astype(v.dtype))


def _mixer_by_heads(h, w, c):
    """``_kda_mixer`` as the parent made it: q, k, v projected by heads
    (``btd,dhk->bthk``: 8 HEADS in a tile's sublanes) and handed so through
    the convolutions to the rule, whose kernels read them flat."""
    dt = c.compute_dtype
    with jax.named_scope("attn_qkv"):
        q, k, v = (jnp.einsum("btd,dhk->bthk", h, w[name].astype(dt))
                   for name in ("wq", "wk", "wv"))
    q, k, v = _conv_silu_by_heads(q, k, v, w["conv_q"], w["conv_k"],
                                  w["conv_v"])
    g, beta = la.gates(h, w)
    with jax.named_scope("attn_core"):
        o = la.gated_delta_rule(q, k, v, g, beta)
    return (la.gated_head_norm(o, h, w, eps=c.norm_eps),
            {"log_decay_min": la.log_decay_min(g)})


def _near(a, b, tol: float = 1e-6) -> bool:
    return float(jnp.abs(a - b).max()) <= tol * (1.0 + float(jnp.abs(b).max()))


# -- (a) on the CPU, tiny widths ----------------------------------------------

GATE_LEAVES = ("f_a", "f_b", "dt_bias", "A_log")


def _gate_case(dtype):
    b, t, d, r, heads, dk = 2, 40, 24, 8, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    w = {"f_a": jax.random.normal(ks[0], (d, r)) * 0.3,
         "f_b": jax.random.normal(ks[1], (r, heads, dk)) * 0.3,
         "dt_bias": jax.random.normal(ks[2], (heads, dk)),
         "A_log": jnp.log(jax.random.uniform(ks[3], (heads,), minval=1.0,
                                             maxval=16.0)),
         "w_beta": jax.random.normal(ks[4], (d, heads))}
    h = jax.random.normal(ks[5], (b, t, d)).astype(dtype)
    weight = jax.random.normal(ks[6], (b, t, heads, dk))
    return h, w, weight


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_flat_g_is_the_parents_viewed_flat(dtype):
    h, w, weight = _gate_case(dtype)
    g, beta = la.gates(h, w)
    g_heads, beta_heads = _gates_by_heads(h, w)
    assert g.shape == (*h.shape[:2], weight.shape[2] * weight.shape[3])
    assert g.dtype == F32 and float(g.max()) <= 0.0
    assert _near(g, g_heads.reshape(g.shape)) and _near(beta, beta_heads)
    # the leaves keep their shapes: so do their gradients
    loss = lambda make: lambda h, w: (  # noqa: E731
        make(h, w)[0].reshape(weight.shape) * weight).sum()
    flat = jax.grad(loss(la.gates), argnums=(0, 1))(h, w)
    heads = jax.grad(loss(_gates_by_heads), argnums=(0, 1))(h, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2    # h's gradient is bfloat16
    assert _near(flat[0].astype(F32), heads[0].astype(F32), tol)
    for name in GATE_LEAVES:
        assert flat[1][name].shape == w[name].shape
        assert _near(flat[1][name], heads[1][name], tol), name


# T = 37: no whole number of 8-token tiles
@pytest.mark.parametrize("dtype,t", [(jnp.float32, 40), (jnp.float32, 37),
                                     (jnp.bfloat16, 40)])
def test_the_flat_head_norm_is_the_parents(dtype, t):
    b, d, r, heads, dv = 2, 24, 8, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    w = {"g_a": jax.random.normal(ks[0], (d, r)) * 0.3,
         "g_b": jax.random.normal(ks[1], (r, heads, dv)) * 0.3,
         "o_norm": 1.0 + 0.1 * jax.random.normal(ks[2], (dv,))}
    h = jax.random.normal(ks[3], (b, t, d)).astype(dtype)
    o = jax.random.normal(ks[4], (b, t, heads, dv)).astype(dtype)
    weight = jax.random.normal(ks[5], o.shape)
    loss = lambda norm: lambda o, h, w: (  # noqa: E731
        norm(o, h, w, eps=1e-5).astype(F32) * weight).sum()
    flat = jax.value_and_grad(loss(la.gated_head_norm), (0, 1, 2))(o, h, w)
    parents = jax.value_and_grad(loss(_head_norm_by_heads), (0, 1, 2))(
        o, h, w)
    assert la.gated_head_norm(o, h, w, eps=1e-5).shape == o.shape
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    for a, b_ in zip(jax.tree.leaves(flat), jax.tree.leaves(parents)):
        assert a.shape == b_.shape and a.dtype == b_.dtype
        assert _near(a.astype(F32), b_.astype(F32), tol)


def _norm_case(dtype, t: int = 136, heads: int = 2, dv: int = 128):
    """Both gates' operands at the kernels' head width: T = 136 is two
    64-token tiles (``small_tiles``) and 8 tokens, so the row is padded
    and ``d_weight`` sums over three grid steps."""
    b, d, r = 1, 24, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    w = {"g_a": jax.random.normal(ks[0], (d, r)) * 0.3,
         "g_b": jax.random.normal(ks[1], (r, heads, dv)) * 0.3,
         "o_norm": 1.0 + 0.1 * jax.random.normal(ks[2], (dv,))}
    h = jax.random.normal(ks[3], (b, t, d)).astype(dtype)
    o = jax.random.normal(ks[4], (b, t, heads, dv)).astype(dtype)
    z = jax.random.normal(ks[5], (b, t, heads * dv)).astype(dtype)
    weight = jax.random.normal(ks[6], o.shape)
    return {"sigmoid": (lambda o, h, w: la.gated_head_norm(
                o, h, w, eps=1e-5), (o, h, w)),
            "silu": (lambda o, z, w: la.silu_gated_head_norm(
                o, z, w["o_norm"], eps=1e-5), (o, z, w))}, weight


@pytest.fixture
def small_tiles():
    """64 tokens a grid step: the cell's 1,024 would make the interpreter
    walk the same code over more rows."""
    with mock.patch.object(la, "_CONV_TOKENS", 64):
        yield


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("gate", ["sigmoid", "silu"])
def test_the_head_norms_kernels_are_the_plain_form(small_tiles, gate, dtype):
    """Interpreted, against ``_head_norm_plain``: the value and every
    gradient (``o``, ``h`` / ``z``, ``g_a``, ``g_b``, ``o_norm``), each in
    its operand's shape and dtype."""
    cases, weight = _norm_case(dtype)
    norm, args = cases[gate]
    both = jax.value_and_grad(
        lambda *a: (norm(*a).astype(F32) * weight).sum(), (0, 1, 2))
    plain = both(*args)
    with mock.patch.object(la, "_on_one_tpu", lambda *a: True):
        fused = both(*args)
    if gate == "silu":      # the leaves of the OTHER gate: no gradient
        assert all(not float(jnp.abs(g[2][name]).max())
                   for g in (plain[1], fused[1]) for name in ("g_a", "g_b"))
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    for a, b_ in zip(jax.tree.leaves(fused), jax.tree.leaves(plain)):
        assert a.shape == b_.shape and a.dtype == b_.dtype
        assert _near(a.astype(F32), b_.astype(F32), tol)


@pytest.mark.parametrize("gate", ["sigmoid", "silu"])
@pytest.mark.parametrize("taken", [True, False], ids=["one_tpu", "elsewhere"])
def test_the_head_norms_choice_follows_on_one_tpu(taken, gate):
    """One rule for the whole mixer: where ``_on_one_tpu`` takes the call,
    the kernels; anywhere else the plain form; asked once, of ``o`` and
    its heads' width."""
    norm, args = _norm_case(F32, t=16)[0][gate]
    asked, ran = [], []
    run = lambda name: lambda o, *a: ran.append(name) or o  # noqa: E731
    with mock.patch.object(la, "_on_one_tpu",
                           lambda a, dk, dv: asked.append((a.shape, dk, dv))
                           or taken), \
            mock.patch.object(la, "_head_norm_kernels", run("kernels")), \
            mock.patch.object(la, "_head_norm_plain", run("plain")):
        norm(*args)
    assert asked == [(args[0].shape, 128, 128)]
    assert ran == ["kernels" if taken else "plain"]


def _rule_case(t: int, heads: int, d: int):
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    ops = (la.l2_norm(jax.random.normal(ks[0], (1, t, heads, d))),
           la.l2_norm(jax.random.normal(ks[1], (1, t, heads, d))),
           jax.random.normal(ks[2], (1, t, heads, d)),
           -0.3 * jax.random.uniform(ks[3], (1, t, heads, d)),
           jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, heads))))
    return ops, jax.random.normal(ks[5], (1, t, heads, d))


@functools.cache
def _rule_and_gradients(rule: str):
    """(the rule's output weighed and summed, its gradient by the five
    operands), jitted once a rule: the by-heads call of both cases below
    is ONE compile (at the kernels' width, interpreted, the longest of
    the file)."""
    return jax.jit(jax.value_and_grad(
        lambda weight, *a: (getattr(la, rule)(*a) * weight).sum(),
        argnums=range(1, 6)))


# T is no whole number of chunks: a flat operand is padded as the others are
@pytest.mark.parametrize("which", [(3,), (0, 1, 2, 3)], ids=["g", "qkvg"])
@pytest.mark.parametrize("rule,t,heads,d", [
    ("gated_delta_rule", 150, 3, 16),   # on the CPU: the scan
    ("_by_scan", 150, 3, 16),
    ("_by_kernels", 136, 2, 128),       # the kernels, interpreted
])
def test_the_rule_takes_g_flat_or_by_heads_and_gives_the_same(rule, t, heads,
                                                              d, which):
    """``g`` alone flat (PR 43) or q, k, v with it (PR 49): the same ``o``
    and the same gradients, each in its operand's own shape."""
    ops, weight = _rule_case(t, heads, d)
    flat = tuple(a.reshape(1, t, heads * d) if i in which else a
                 for i, a in enumerate(ops))
    with jax.default_matmul_precision("highest"):
        both = _rule_and_gradients(rule)
        (o4, grads4), (o3, grads3) = both(weight, *ops), both(weight, *flat)
    assert float(o3) == float(o4)
    for a, b, given in zip(grads3, grads4, flat):
        assert a.shape == given.shape               # comes back as it went
        assert float(jnp.abs(a.reshape(b.shape) - b).max()) == 0.0


@pytest.mark.parametrize("t", [128, 150])
def test_the_counter_reads_the_same_for_both(t):
    g = _rule_case(t, 3, 16)[0][3]
    flat = g.reshape(1, t, -1)
    assert float(la.log_decay_min(flat)) == float(la.log_decay_min(g)) < 0
    assert float(jax.grad(lambda g: la.log_decay_min(g))(flat).sum()) == 0.0


# -- (b) compiled for a described v5e, the cell's widths ------------------------

B, T, HEADS, DK, RANK, D_MODEL = 1, 1024, 32, 128, 128, 2304


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache without a chip: keep these out of it
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _mixer_hlo(device) -> str:
    """The optimised HLO of ``value_and_grad`` of ``_kda_mixer`` under
    ``jax.checkpoint`` (forward, recompute, backward: a layer of the train
    step) at the cell's widths, compiled for ``device``."""
    c = transformer.kimi_linear_48b_a3b(n_layers=5)
    assert (c.kda_heads, c.kda_head_dim, c.d_model) == (HEADS, DK, D_MODEL)
    one = jax.sharding.SingleDeviceSharding(device)
    w = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype, sharding=one),
        c.shapes()["layers"]["kda"])
    assert w["f_b"].shape == (RANK, HEADS, DK)
    h = jax.ShapeDtypeStruct((B, T, D_MODEL), c.compute_dtype, sharding=one)

    @jax.checkpoint
    def layer(h, w):
        o, counters = mixers._kda_mixer(h, w, c)
        return jnp.square(o.astype(F32)).sum() + counters["log_decay_min"]

    # ``gated_delta_rule`` asks ``jax.devices()``, which here is the CPU's:
    # steer it, in the test, to the described chip, as the rehearsal does
    with mock.patch.object(jax, "devices", lambda *a, **k: [device]):
        lowered = jax.jit(jax.value_and_grad(layer, argnums=(0, 1))).lower(
            h, w)
    assert "tpu_custom_call" in lowered.as_text()       # the kernels
    return lowered.compile().as_text()


_INSTRUCTION = re.compile(
    r"= (?:f32|bf16)\[([\d,]+)\]\S* (copy|transpose|reshape)\(")


def _relayouts(hlo: str, sizes=(B * T * HEADS * DK,)) -> list[str]:
    """Every ``copy``, ``transpose`` and ``reshape`` (one that is no
    bitcast stays a ``reshape`` in optimised HLO) whose result is float32
    or bfloat16 with ``B * T * H * dk`` elements (``sizes``), inside fusions
    too,
    under ANY scope or none: the projections' (``attn_qkv``), the
    convolutions' (``kda_conv``), the rule's (``attn_core``), the gates'
    and the head norm's (``kda_gate``: PR 43's contract, which the
    parent's mixer keeps in bfloat16 too, so no scope is exempted)."""
    found = []
    for line in hlo.splitlines():
        m = _INSTRUCTION.search(line)
        if m and math.prod(map(int, m.group(1).split(","))) in sizes:
            found.append(line.strip()[:400])
    return found


def _kernels_under(hlo: str, scope: str) -> int:
    """Pallas calls whose ``op_name`` holds ``scope``."""
    return sum("tpu_custom_call" in line and f"/{scope}/" in line.replace(
        f"({scope})", f"/{scope}/") for line in hlo.splitlines()
        if " custom-call(" in line)


_RESULT = re.compile(r"^\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) "
                     r"(?:fusion|custom-call|convolution)\(")


def _float32_written_under(hlo: str, scope: str,
                           sizes=(B * T * HEADS * DK,)) -> list[str]:
    """The instructions OUTSIDE fused computations (a fusion, a kernel, a
    matmul: what writes its result to memory) under ``scope`` with a
    float32 result of ``B * T * H * dk`` elements (``sizes``), tuples'
    parts too."""
    found, fused = [], False
    for line in hlo.splitlines():
        if line[:1] in "%E":                    # a computation opens
            fused = line.startswith("%fused_computation")
        m = None if fused else _RESULT.match(line)
        if m and f"/{scope}/" in line.replace(f"({scope})", f"/{scope}/") \
                and any(math.prod(map(int, dims.split(","))) in sizes
                        for dims in re.findall(r"f32\[([\d,]+)\]",
                                               m.group(1))):
            found.append(line.strip()[:400])
    return found


def test_no_array_of_the_operands_size_changes_its_tiling(chip):
    hlo = _mixer_hlo(chip)
    assert "kda_conv" in hlo and "/kda_gate/" in hlo       # the scopes' names
    assert _relayouts(hlo) == []
    # a chain a Pallas call: three forward, three recomputed, three backward
    assert _kernels_under(hlo, "kda_conv") == 9
    assert _kernels_under(hlo, "attn_core") == 3
    # the head norm a Pallas call: forward, recomputed, backward; and under
    # ``kda_gate`` no float32 [B, T, H * dv] array is written but ``g``'s
    # (``gates``' matmul: forward ``g``, recomputed ``g`` and softplus'
    # argument): no pre-activation of the output gate, no spread statistic
    assert _kernels_under(hlo, "kda_gate") == 3
    written = _float32_written_under(hlo, "kda_gate")
    assert written and all("btr,rc->btc/dot_general" in line
                           for line in written), written
    assert len(written) <= 2


@pytest.mark.parametrize("where,name,parents", [
    (la, "gates", _gates_by_heads),
    (la, "gated_head_norm", _head_norm_by_heads),
    (mixers, "_kda_mixer", _mixer_by_heads)],
    ids=["gates", "gated_head_norm", "_kda_mixer"])
def test_the_parents_forms_do_and_the_assertion_sees_it(chip, where, name,
                                                        parents):
    with mock.patch.object(where, name, parents):
        found = _relayouts(_mixer_hlo(chip))
    assert found, f"{name} by heads should cross between two tilings"
    if where is mixers:
        # q, k, v into the kernels' tiling (bfloat16, forward and recompute)
        # and the chains' backward with the positions in the lanes (float32)
        assert sum("bf16[" in line and "attn_core" in line
                   for line in found) >= 6
        assert sum("f32[" in line and "kda_conv" in line
                   for line in found) >= 3


def test_the_state_space_scans_kernels_compile_at_the_cells_widths(chip):
    """Mosaic takes ``ops/state_space.py``'s two kernels at Nemotron-3-Nano's
    widths (64 heads of 64 in 8 groups, state 128: a 512-lane group a step,
    its state [128, 512] float32 in VMEM), which interpret mode cannot
    show; forward with the chunk-start states, and backward."""
    from ray_tpu.ops import state_space as ss

    one = jax.sharding.SingleDeviceSharding(chip)
    like = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)
    x, dt, b = (like((B, T, 64 * 64), jnp.bfloat16), like((B, T, 64), F32),
                like((B, T, 8 * 128), jnp.bfloat16))
    starts = like((B, T // 128, 128, 64 * 64), F32)
    for backward, operands in ((False, (x, dt, dt, b, b)),
                               (True, (x, dt, dt, b, b, starts, x))):
        text = ss._launch.lower(backward, not backward, False,
                                *operands).compile().as_text()
        assert "tpu_custom_call" in text


# -- (c) the state-space mixer, compiled for the same described device ----------

SSM_SIZES = (B * T * 4096, B * T * 6144)    # y / z; the chain's [x | B | C]


def _ssm_hlo(device) -> str:
    """``_mixer_hlo`` for ``_ssm_mixer`` at Nemotron-3-Nano's widths (64
    heads of 64 in 8 groups of 512 lanes, state 128, four taps and a bias
    over 6,144 lanes): forward, recompute and backward of a layer."""
    c = transformer.nemotron_3_nano_30b_a3b(n_layers=1)
    assert c.layer_mixers == ("ssm",) and c.ssm_conv_bias
    assert (c.kda_heads * c.kda_head_dim, c.ssm_groups) == (4096, 8)
    one = jax.sharding.SingleDeviceSharding(device)
    w = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype, sharding=one),
        c.shapes()["layers"]["ssm"])
    assert w["conv_w"].shape == (4, 6144) and w["o_norm"].shape == (4096,)
    h = jax.ShapeDtypeStruct((B, T, c.d_model), c.compute_dtype, sharding=one)

    @jax.checkpoint
    def layer(h, w):
        o, counters = mixers._ssm_mixer(h, w, c)
        return jnp.square(o.astype(F32)).sum() + sum(counters.values())

    with mock.patch.object(jax, "devices", lambda *a, **k: [device]):
        lowered = jax.jit(jax.value_and_grad(layer, argnums=(0, 1))).lower(
            h, w)
    return lowered.compile().as_text()


def test_the_state_space_mixer_stays_flat_between_its_kernels(chip):
    """Mosaic takes the chain with a bias row and the norm with a 512-lane
    group; a layer is three Pallas calls each for the chain, the scan and
    the norm (forward, recomputed, backward); under ``kda_conv`` and
    ``kda_gate`` no array of ``y``'s or ``[x | B | C]``'s size changes its
    tiling, and under ``kda_gate`` none is written in float32."""
    hlo = _ssm_hlo(chip)
    assert [_kernels_under(hlo, scope) for scope
            in ("kda_conv", "attn_core", "kda_gate")] == [3, 3, 3]
    assert [line for line in _relayouts(hlo, SSM_SIZES)
            if "kda_gate" in line or "kda_conv" in line] == []
    assert _float32_written_under(hlo, "kda_gate", SSM_SIZES) == []


def test_the_parents_group_norm_does_relayout_and_the_assertion_sees_it(chip):
    """``gated_group_norm``'s plain body where the kernels were (PR 59's
    tree): the norm's ``[T, 8, 512]`` view of the scan's flat ``y`` is
    another tiling, float32 arrays of ``y``'s size cross under
    ``kda_gate``, and no kernel runs there."""
    from ray_tpu.ops import state_space as ss

    with mock.patch.object(ss, "_norm_takes_kernels", lambda *a: False):
        hlo = _ssm_hlo(chip)
    assert _kernels_under(hlo, "kda_gate") == 0
    assert _kernels_under(hlo, "kda_conv") == 3
    assert [line for line in _relayouts(hlo, SSM_SIZES)
            if "kda_gate" in line and "f32[" in line]
