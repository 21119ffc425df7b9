"""The token mixers a layer may run, ONE record each (``MIXERS``).

``transformer._block`` knows a mixer as a ``Mixer`` and nothing else: where
its own leaves live, how they are drawn and sharded, what a configuration
has to give it, the scope it runs under, the function from a block's
normed input to what ``attn/wo`` projects (or, ``own_out``, to what joins
the stream: an inner width of its own, its output projection among its own
leaves), the memory it hands later layers or reads from an earlier one
(``writes`` / ``reads``), the statistics it reports (``Counter``), why the
KV-cache decode refuses it. ``layer_mixers`` names a layer's mixer by its
key; a model without it runs ``"attn"`` everywhere.

  - ``"attn"``: softmax attention, plain (``_plain_qkv``: fused or GQA
    projections, QK-norm of all heads together or a head, RoPE on a head's
    whole width or its first ``rope_fraction``, an output gate
    ``sigmoid(W_g h)``) or, with ``kv_latent``, latent (``_latent_qkv``),
    or, with ``diff_attn``, DIFFERENTIAL (``_diff_core``: two softmax
    maps a pair of heads, ``a_1 - lambda a_2``, a norm a pair).
  - ``"kda"``, ``"gdn"``, ``"ssm"``, the LINEAR ones (a state carried along
    the sequence, under ``attn_linear``): Kimi Delta Attention
    (``_kda_mixer``), Gated DeltaNet (``_gdn_mixer``), a Mamba-2
    state-space layer (``_ssm_mixer``), a Mamba-1 selective-scan layer
    (``"ssm1"``, ``_ssm1_mixer``: 2 x the stream wide, its own ``wo``).
  - ``"gmu"`` and ``"cross"``, the READERS of a memory (no state of their
    own): a gated memory unit ``W_2 (m * silu(W_1 h))`` on the scan output
    ``m`` the last ``"ssm1"`` layer ahead of it handed out, and differential
    cross-attention whose keys and values are the last ``"attn"`` layer's.

**To add a mixer**: its record here, its line in ``MIXERS``, its fields in
``TransformerConfig``; nothing in ``transformer.py``'s block, loss, init or
specs (``tests/test_mixers.py`` registers one more from outside and trains
it). A record's ``apply`` returns ``(o, counters)``: ``o`` is what
``attn/wo``, ONE stack over every layer whose mixer has attention's inner
width, projects; a record with ``own_out`` keeps its output projection
among its own leaves and returns what joins the stream. A record that
``writes`` returns a third value, {its memory's name: the arrays}: the
stack keeps it from the last such layer ahead of the first layer whose
record ``reads`` that name, and hands it to every reader in ``ctx.memory``.
This file opens scopes of the step: one of ``transformer.SCOPE_FILES``.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import linear_attention, state_space
from ray_tpu.ops.attention import attention
from ray_tpu.ops.layers import apply_rope, rms_norm
from ray_tpu.parallel.mesh import (AXIS_DATA, AXIS_FSDP, AXIS_SEQUENCE,
                                   AXIS_TENSOR)

_BATCH = (AXIS_DATA, AXIS_FSDP)

# Inside ``attn``, for a model with a ``layer_pattern``, latent attention
# or ``layer_mixers``: which kind of layer the instruction belongs to.
# ``attn_linear`` is everything of a linear layer's mixer: ``attn_qkv`` (its
# projections), ``ops.linear_attention.SCOPES`` (``kda_conv``, ``kda_gate``),
# ``attn_core`` (the delta rule or the state-space scan, nothing else: on one
# TPU chip two Pallas kernels, elsewhere XLA with ``ssm_carry``), ``attn_out``.
ATTN_SCOPES = ("attn_full", "attn_window", "attn_linear")
# Latent attention only: what the latent form adds outside the kernels
# (down-projection, the latent's norm, up-projection, RoPE on the rotary
# parts). The output gate: its projection, the sigmoid, the product.
MLA_SCOPE = "mla_latent"
GATE_SCOPE = "attn_gate"
# Differential attention outside the kernels (the pairs laid out, the
# ``lambda``s, ``a_1 - lambda a_2``, the pair norm, the scale); everything
# of a cross layer's mixer but ``attn_out`` (inside ``attn_full``); a gated
# memory unit's two projections and product (inside ``attn_linear``).
DIFF_SCOPE = "attn_diff"
CROSS_SCOPE = "attn_cross"
GMU_SCOPE = "gmu"
# What attention does, part by part: ``attn_qkv`` the projections (latent
# attention: the query's alone, the latent's are ``mla_latent``),
# ``attn_pos`` QK-norm and RoPE, ``attn_gqa`` k and v repeated to the query
# heads, ``attn_core`` the one ``attention(...)`` call (the kernels and what
# ``ops.attention.SCOPES`` names around them, or the materialised scores,
# softmax and ``p v``), ``attn_out`` the output projection and the residual
# add (``transformer._mixer_sublayer``).
ATTN_PART_SCOPES = ("attn_qkv", "attn_pos", "attn_gqa", "attn_core",
                    "attn_out")


class Counter(NamedTuple):
    """One per-layer statistic of a step, named ONCE: a sublayer hands it
    out under ``key`` (a layer with no such sublayer reads zeros of
    ``shape``), ``forward`` folds the layers' values by ``fold``
    (``transformer._fold``: "mean" over the layers that have the sublayer,
    "max", "min", "kind mean" = the sum over the number of layers that
    report it, "stack" = their rows as they are) and ``lm_loss`` reports
    the result as ``metric``, times the configuration's field ``weight`` in
    the loss where it names one. ``has``: whether a model reports it."""
    key: str
    metric: str
    fold: str
    has: Callable[[Any], bool] = lambda c: True
    shape: Callable[[Any], tuple] = lambda c: ()
    weight: str | None = None


# the output gate's mean over the attention layers; the most negative
# cumulative log-decay inside any chunk of any linear layer; the mean step
# ``Delta`` over the state-space layers' tokens and heads
GATE_MEAN = Counter("gate_mean", "attn_gate_mean", "kind mean")
LOG_DECAY_MIN = Counter("log_decay_min", "kda_log_decay_min", "min")
STEP_MEAN = Counter("step_mean", "ssm_step_mean", "kind mean")
# differential attention's ``lambda``, a mean over the attention and cross
# layers; a memory unit's gate ``silu(W_1 h)``, a mean over its layers
DIFF_LAMBDA = Counter("diff_lambda", "attn_diff_lambda", "kind mean")
GMU_GATE_MEAN = Counter("gmu_gate_mean", "gmu_gate_mean", "kind mean")


class Ctx(NamedTuple):
    """What of a layer only attention reads: (cos, sin) or None where
    nothing is rotated, the tokens' positions, the sliding window's width
    or None, the mesh's sharding constraint; ``memory``: {name: what an
    earlier layer handed out} for a record that ``reads``; ``layer``: the
    layer's published number (a traced scalar) where a record asks it."""
    rope: Any
    positions: Any
    window: int | None
    con: Callable
    memory: Any = None
    layer: Any = None


class Draw(NamedTuple):
    """How ``init_params`` draws: ``norm(key, *shape, s=0.02)`` in the
    parameters' dtype, ``uniform(key, *shape, low=, high=)`` float32,
    ``unit(*shape)`` a norm's weight that scales by 1, the residual-out
    projections' deviation, the keys the output gate draws from."""
    norm: Callable
    uniform: Callable
    unit: Callable
    res_std: float
    gate_keys: Any


class Mixer(NamedTuple):
    """Everything the model knows of one kind of token mixer."""
    stack: Callable     # c -> the stack's subtree that holds its own leaves
    scope: Callable     # window or None -> its sub-scope of ``attn``
    init: Callable      # (c, keys, n, draw) -> its leaves for n layers
    specs: Callable     # c -> their megatron-style specs (none: replicated)
    # c -> (what a layer of this kind needs: (words, met) or None, rows
    # (words, wrong) of what ``layer_mixers`` does not run with)
    check: Callable
    # (h [B, T, D] normed, its own leaves, c, ctx) -> (what ``attn/wo``
    # projects, {a counter's key: its value}[, {``writes``: the memory}])
    apply: Callable
    counters: Callable  # c -> the counters a layer of this kind reports
    decodes: Callable | None    # c -> why decode refuses it; None: it runs
    # ``apply`` returns what JOINS THE STREAM [B, T, D]: the record's inner
    # width is its own and so is its output projection (no ``attn/wo`` row)
    own_out: bool = False
    reads: str | None = None    # the memory ``apply`` finds in ``ctx.memory``
    writes: str | None = None   # the memory ``apply`` hands out (third value)


def counters_of(c) -> dict:
    """{a counter the model's mixers report: the layers (of ``n_layers``)
    that report it}, in the order of ``MIXERS``."""
    found: dict = {}
    for kind, mixer in MIXERS.items():
        layers = c.layers_with(kind)
        for counter in mixer.counters(c) if layers else ():
            found[counter] = tuple(sorted(found.get(counter, ()) + layers))
    return found


def _norm_weight(c, w):
    """What a norm scales by, from its leaf: ``1 + w`` where the model's
    norms are zero-centred (``norm_zero_centred``), in float32."""
    return 1.0 + w.astype(jnp.float32) if c.norm_zero_centred else w


# -- attention --------------------------------------------------------------------

def _attn_init(c, keys, n, draw):
    """Attention's leaves ``wq``, ``wk``, ``wv``, ``wo`` (with ``qk_norm``
    ``q_norm`` / ``k_norm``, a head's or all heads'; with ``attn_gate``
    ``wg``, plain attention's fused q-and-gate projection as two leaves);
    with ``attn_bias`` ``bq``, ``bk``, ``bv``, ``bo``, N(0, 0.02) like a
    matrix: a program that dropped a zero bias would compute what a sound
    one does; with ``diff_attn`` ``_diff_leaves``);
    latent attention's ``wq``, ``wkv_a`` ([latent ; the one rotary key]
    down), ``kv_norm`` and ``wkv_b`` ([k_nope ; v] of every head up)."""
    norm, unit = draw.norm, draw.unit
    D, H, KV, Dh = c.d_model, c.n_heads, c.kv_heads, c.head_dim
    if c.kv_latent is None:
        first = next(keys)
        stack = {
            "wq": norm(first, n, D, H, Dh),
            "wk": norm(next(keys), n, D, KV, Dh),
            "wv": norm(next(keys), n, D, KV, Dh),
            "wo": norm(next(keys), n, H, Dh, D, s=draw.res_std),
        }
        # what ``attn_bias`` and ``diff_attn`` add draws from keys of its
        # own, so every other model's weights stay what the seed gave
        more = iter(jax.random.split(jax.random.fold_in(first, 1), 8))
        if c.attn_bias:
            stack.update(bq=norm(next(more), n, H, Dh),
                         bk=norm(next(more), n, KV, Dh),
                         bv=norm(next(more), n, KV, Dh),
                         bo=norm(next(more), n, D))
        if c.diff_attn:
            stack.update(_diff_leaves(c, more, n, draw))
        if c.qk_norm:
            per_head = c.qk_norm == "head"
            stack["q_norm"] = unit(n, Dh if per_head else H * Dh)
            stack["k_norm"] = unit(n, Dh if per_head else KV * Dh)
        if c.attn_gate:
            stack["wg"] = norm(next(draw.gate_keys), n, D, H, Dh)
        return stack
    # [latent ; the one rotary key] down, the latent's norm, then
    # [k_nope ; v] of every head up.
    return {
        "wq": norm(next(keys), n, D, H, Dh),
        "wkv_a": norm(next(keys), n, D, c.kv_latent + c.d_head_rope),
        "kv_norm": jnp.ones((n, c.kv_latent), jnp.dtype(c.param_dtype)),
        "wkv_b": norm(next(keys), n, c.kv_latent, H,
                      c.d_head_nope + c.d_head_v),
        "wo": norm(next(keys), n, H, c.d_head_v, D, s=draw.res_std),
    }


def _attn_specs(c):
    return {
        "wq": P(None, None, AXIS_TENSOR, None),
        "wk": P(None, None, AXIS_TENSOR, None),
        "wv": P(None, None, AXIS_TENSOR, None),
        "wo": P(None, AXIS_TENSOR, None, None),
        # the output gate shards by head like wq; a head's q / k norm
        # weights are every head's (no entry: replicated)
        "wg": P(None, None, AXIS_TENSOR, None),
        # latent attention: the down-projection and the latent's norm are
        # every head's; the up-projection shards by head like wq
        "wkv_b": P(None, None, AXIS_TENSOR, None),
    }


def _attention(h, w, c, ctx: Ctx):
    """Softmax attention up to (not with) the output projection: the
    operands (``_plain_qkv`` / ``_latent_qkv``), the ONE ``attention(...)``
    call under ``attn_core``, the output gate."""
    if c.diff_attn:
        q1, q2 = _diff_queries(h, w, c)
        with jax.named_scope("attn_qkv"):
            k1, k2 = (_project(h, wk, bk, c) for wk, bk in zip(
                _halves(w["wk"]), _halves(w.get("bk"))))
            v = _project(h, _side_by_side(w["wv"]),
                         _side_by_side(w.get("bv")), c)
        o, counters = _diff_core(q1, q2, k1, k2, v, w, c, ctx)
        return o, counters, {"kv": (k1, k2, v)}
    if c.kv_latent is not None:
        q, k, v, shared = _latent_qkv(h, w, c, ctx.rope, ctx.positions)
    else:
        q, k, v = _plain_qkv(h, w, c, ctx.rope, ctx.positions)
        shared = {}
    q = ctx.con(q, _BATCH, AXIS_SEQUENCE, AXIS_TENSOR, None)
    with jax.named_scope("attn_core"):
        o = attention(q, k, v, causal=True, impl=c.attn_impl,
                      window=ctx.window, **shared)
    if not c.attn_gate:
        return o, {}
    o, gate_mean = _gate_output(o, h, w["wg"])
    return o, {GATE_MEAN.key: gate_mean}


def _gate_output(o, h, wg):
    """Attention's output ``o`` [B, T, H, Dh] times ``sigmoid(W_g h)`` of
    the block's normed input ``h`` [B, T, D] -> (gated o, the gate's mean:
    0.5 at a seeded init, 0 where the gate has shut and attention is paid
    for by nobody). The sigmoid and the product are float32."""
    with jax.named_scope(GATE_SCOPE):
        gate = jax.nn.sigmoid(jnp.einsum(
            "btd,dhk->bthk", h, wg.astype(h.dtype),
            preferred_element_type=jnp.float32))
        return (o * gate).astype(o.dtype), jax.lax.stop_gradient(gate).mean()


# Differential attention (arXiv:2410.05258, as Phi-4-mini-flash's
# ``FlashDiffCustomAttention`` has it). The heads PAIR UP, pair ``n`` heads
# ``2 n`` and ``2 n + 1`` (interleaved: the implementation's ``reshape(..,
# heads // 2, 2, head_dim)``): H / 2 query pairs ``(q1, q2)``, KV / 2 key
# pairs ``(k1, k2)``, KV / 2 values 2 x head_dim wide (a pair's two value
# heads side by side); query pair ``n`` reads key / value pair ``n // (H /
# KV)``. ``a_i = softmax(q_i k_i^T / sqrt(head_dim), causal[, window]) v``,
# ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)``,
# ``lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)`` by the layer's PUBLISHED
# number, ``o = (1 - lambda_init(l)) rmsnorm(a_1 - lambda a_2) w_sub`` over a
# pair's 2 x head_dim channels. The WEIGHTS are split by the pair, never
# the activations (``_latent_qkv`` says why): each projection's own output
# is what a kernel reads. Two ``attention(...)`` calls a layer, head_dim-
# wide scores against ONE 2 x head_dim-wide value: no kernel of its own.

def _diff_leaves(c, keys, n, draw):
    """The four ``lambda`` vectors N(0, 0.1) as ONE leaf ``lambdas`` [4,
    head_dim] (``lq1``, ``lk1``, ``lq2``, ``lk2``: a vector's elements all
    move by one scalar's gradient, so four leaves of 64 would be the
    tree's smallest and say little of a step) and the pair norm's weight
    1."""
    return {"lambdas": draw.norm(next(keys), n, 4, c.head_dim, s=0.1),
            "sub_norm": jnp.ones((n, 2 * c.head_dim),
                                 jnp.dtype(c.param_dtype))}


def _halves(a):
    """A leaf over heads that pair up, [.., 2 n, Dh] -> (the pairs' first
    heads, their second) [.., n, Dh] each; None (no bias) stays None."""
    if a is None:
        return None, None
    pairs = a.reshape(*a.shape[:-2], a.shape[-2] // 2, 2, a.shape[-1])
    return pairs[..., 0, :], pairs[..., 1, :]


def _side_by_side(a):
    """[.., 2 n, Dh] -> [.., n, 2 Dh]: a pair's two heads side by side."""
    if a is None:
        return None
    return a.reshape(*a.shape[:-2], a.shape[-2] // 2, 2 * a.shape[-1])


def _project(h, weight, bias, c):
    """``h W + b`` by heads: ``weight`` [D, heads, width], ``bias`` [heads,
    width] or None -> [B, T, heads, width]."""
    dt = c.compute_dtype
    out = jnp.einsum("btd,dhk->bthk", h, weight.astype(dt))
    return out if bias is None else out + bias.astype(dt)


def _diff_queries(h, w, c):
    """(q1, q2) [B, T, H / 2, Dh] of the normed input ``h``."""
    with jax.named_scope("attn_qkv"):
        return tuple(_project(h, wq, bq, c) for wq, bq in zip(
            _halves(w["wq"]), _halves(w.get("bq"))))


def _diff_core(q1, q2, k1, k2, v, w, c, ctx: Ctx):
    """Differential attention from its operands (the comment above) ->
    (o [B, T, H / 2, 2 Dh], {``lambda``}). ``attn_core`` holds the TWO
    ``attention(...)`` calls, ``attn_diff`` the ``lambda``s, the combine,
    the pair norm and the scale, in float32."""
    rep = q1.shape[2] // k1.shape[2]
    if rep > 1:
        with jax.named_scope("attn_gqa"):
            k1, k2, v = (jnp.repeat(a, rep, axis=2) for a in (k1, k2, v))
    q1 = ctx.con(q1, _BATCH, AXIS_SEQUENCE, AXIS_TENSOR, None)
    with jax.named_scope("attn_core"):
        a1, a2 = (attention(q, k, v, causal=True, impl=c.attn_impl,
                            window=ctx.window)
                  for q, k in ((q1, k1), (q2, k2)))
    with jax.named_scope(DIFF_SCOPE):
        f32 = lambda name: w[name].astype(jnp.float32)
        init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(ctx.layer, jnp.float32))
        lq1, lk1, lq2, lk2 = f32("lambdas")
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init
        d = a1.astype(jnp.float32) - lam * a2.astype(jnp.float32)
        o = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + c.norm_eps)
        o = (o * ((1.0 - init) * f32("sub_norm"))).astype(a1.dtype)
        return o, {DIFF_LAMBDA.key: jax.lax.stop_gradient(lam)}


def _plain_qkv(h, w, c, rope, positions):
    """q, k, v [B, T, H, Dh] of plain attention from the normed input
    ``h`` [B, T, D]: projections, QK-norm, RoPE, k and v repeated to the
    query heads."""
    dt = c.compute_dtype
    with jax.named_scope("attn_qkv"):
        if c.kv_heads == c.n_heads:
            # Fused QKV: one (d → 3·h·k) matmul keeps the MXU busier than
            # three skinny d→d projections (the weight concat is a few MB,
            # amortized by XLA across the fused step).
            wqkv = jnp.concatenate(
                [w["wq"].astype(dt), w["wk"].astype(dt), w["wv"].astype(dt)],
                axis=-1,
            )  # [d, h, 3k]
            qkv = jnp.einsum("btd,dhm->bthm", h, wqkv)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q = jnp.einsum("btd,dhk->bthk", h, w["wq"].astype(dt))
            k = jnp.einsum("btd,dhk->bthk", h, w["wk"].astype(dt))
            v = jnp.einsum("btd,dhk->bthk", h, w["wv"].astype(dt))
    with jax.named_scope("attn_pos"):
        if c.qk_norm == "head":         # a head at a time, one weight
            q = rms_norm(q, _norm_weight(c, w["q_norm"]), eps=c.norm_eps)
            k = rms_norm(k, _norm_weight(c, w["k_norm"]), eps=c.norm_eps)
        elif c.qk_norm:
            q = _qk_norm(q, w["q_norm"])
            k = _qk_norm(k, w["k_norm"])
        if rope is not None:
            cos, sin = rope
            q = apply_rope(q, cos, sin, positions=positions)
            k = apply_rope(k, cos, sin, positions=positions)
    return (q, *_expand_gqa(k, v, c))


def _latent_qkv(h, w, c, rope, positions):
    """Latent attention's operands from the normed input ``h`` [B, T, D]:
    (q_nope [B, T, H, nope], k_nope [B, T, H, nope], v [B, T, H, v],
    {"q_shared": q_rope [B, T, H, rope], "k_shared": k_rope [B, T,
    rope]}). Keys and values come up from ONE ``kv_latent``-wide normed
    compression of the token; the rotary part of the key is one vector a
    token, which every head scores its own rotary query part against
    (``ops.attention``: ``q_shared`` / ``k_shared``). RoPE pairs the
    halves of the rotary part, as ``apply_rope`` does everywhere. With
    ``rope`` None (``latent_rope`` False) nothing is rotated: the shared
    part is a plain key part, and ``attn_pos`` stays empty.

    The WEIGHTS are split (``wq`` into its no-position and rotary columns,
    ``wkv_b`` into its key and value columns), never the ``[B, T, H, 192]``
    / ``[B, T, H, 256]`` activations: a slice between a matmul and a
    custom call cannot fuse into either, so each was a copy of the whole
    operand (``[2, 32, 8192, 128]``: 0.43 ms, v5e), where a projection's
    own output is written head-major as the kernels read it. The tree
    keeps ONE ``wq`` and ONE ``wkv_b``."""
    dt = c.compute_dtype
    nope, latent = c.d_head_nope, c.kv_latent
    with jax.named_scope("attn_qkv"):
        wq = w["wq"].astype(dt)
        q_nope = jnp.einsum("btd,dhk->bthk", h, wq[..., :nope])
        q_rope = jnp.einsum("btd,dhk->bthk", h, wq[..., nope:])
    with jax.named_scope(MLA_SCOPE):
        down = jnp.einsum("btd,dc->btc", h, w["wkv_a"].astype(dt))
        normed = rms_norm(down[..., :latent], w["kv_norm"], eps=c.norm_eps)
        wkv_b = w["wkv_b"].astype(dt)
        k_nope = jnp.einsum("btc,chk->bthk", normed, wkv_b[..., :nope])
        v = jnp.einsum("btc,chk->bthk", normed, wkv_b[..., nope:])
        k_rope = down[..., latent:]
        if rope is not None:
            cos, sin = rope
            with jax.named_scope("attn_pos"):
                # RoPE reads the projection ROUNDED to ``dt``, as the
                # kernels read q_nope: left to itself XLA hands it the
                # matmul's float32 accumulator (excess precision), and
                # the forward is no longer the one ``correct`` was set on.
                bits = jnp.finfo(dt)
                q_rope = apply_rope(
                    jax.lax.reduce_precision(q_rope, bits.nexp, bits.nmant),
                    cos, sin, positions=positions)
                k_rope = apply_rope(k_rope[:, :, None], cos, sin,
                                    positions=positions)[:, :, 0]
        return (q_nope, k_nope, v, {"q_shared": q_rope, "k_shared": k_rope})


def _qk_norm(x, weight):
    """RMSNorm of a q or k projection [B, T, H, Dh] over ALL its heads
    together (H * Dh values a token), as OLMoE norms them."""
    return rms_norm(x.reshape(*x.shape[:2], -1), weight).reshape(x.shape)


def _expand_gqa(k, v, c):
    if c.kv_heads == c.n_heads:
        return k, v
    rep = c.n_heads // c.kv_heads
    with jax.named_scope("attn_gqa"):
        return (jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2))


# -- the linear mixers ------------------------------------------------------------

def _linear_rows(c, name: str, same_heads: bool = False) -> tuple:
    """What every linear mixer asks of ``layer_mixers``: its sizes, and as
    many values a token for ``attn/wo``, ONE stack over every mixer layer,
    as attention's heads hand it: a linear layer's heads are its rows
    regrouped (32 x 128 of Gated DeltaNet's for 16 x 256); ``same_heads``
    (KDA): the same heads."""
    ours = (c.kda_heads, c.kda_head_dim)
    wo = (c.n_heads, c.d_head_v if c.kv_latent is not None else c.head_dim)
    return (("kda_heads, kda_head_dim or kda_conv < 1",
             min(c.kda_heads, c.kda_head_dim, c.kda_conv) < 1),
            (f"{name} heads "
             f"{ours} that are not attention's "
             f"(n_heads, a value's width) {wo}",
             ours != wo if same_heads else math.prod(ours) != math.prod(wo)))


def refuses_linear(c) -> str:
    return (
        f"KV-cache decode does not run a model with layer_mixers "
        f"({c.layer_mixers!r}; kda_heads {c.kda_heads}, kda_head_dim "
        f"{c.kda_head_dim}, kda_conv {c.kda_conv}, linear_key_heads "
        f"{c.linear_key_heads}): a linear (KDA or Gated DeltaNet) layer "
        f"keeps a recurrent state and its convolution's last positions, "
        f"not keys and values, and would be decoded as plain attention")


def refuses_single_sublayers(c) -> str:
    return (
        f"KV-cache decode does not run a stack of single-sublayer blocks "
        f"or a state-space layer (layer_mixers {c.layer_mixers!r}; "
        f"kda_heads {c.kda_heads}, kda_head_dim {c.kda_head_dim}, "
        f"ssm_state {c.ssm_state}, ssm_groups {c.ssm_groups}, kda_conv "
        f"{c.kda_conv}): a state-space ('ssm') layer keeps a recurrent "
        f"state [heads, channels, ssm_state] and its convolution's last "
        f"positions, not keys and values, and a layer named 'ffn' has "
        f"no mixer and so no cache at all, where every layer would be "
        f"decoded as attention AND an FFN")


def _step_bias(step, pdt):
    """``dt_bias``: the inverse softplus of the drawn step."""
    return (step + jnp.log(-jnp.expm1(-step))).astype(pdt)


def _kda_init(c, keys, n, draw):
    """A linear mixer's init is its public implementation's: the
    convolutions U(-1 / sqrt(taps), 1 / sqrt(taps)), ``A_log`` = log U(1,
    16), ``dt_bias`` the inverse softplus of a log-uniform step in [0.001,
    0.1]; the low-rank maps' rank is the head width."""
    norm, uniform, pdt = draw.norm, draw.uniform, jnp.dtype(c.param_dtype)
    D, Hk, dk, taps = c.d_model, c.kda_heads, c.kda_head_dim, c.kda_conv
    edge = 1.0 / math.sqrt(taps)
    step = jnp.exp(uniform(next(keys), n, Hk, dk, low=math.log(1e-3),
                           high=math.log(1e-1)))
    return {
        **{f"w{x}": norm(next(keys), n, D, Hk, dk) for x in "qkv"},
        **{f"conv_{x}": uniform(next(keys), n, taps, Hk, dk, low=-edge,
                                high=edge).astype(pdt) for x in "qkv"},
        "f_a": norm(next(keys), n, D, dk),
        "f_b": norm(next(keys), n, dk, Hk, dk),
        "dt_bias": _step_bias(step, pdt),
        "A_log": jnp.log(uniform(next(keys), n, Hk, low=1.0,
                                 high=16.0)).astype(pdt),
        "w_beta": norm(next(keys), n, D, Hk),
        "g_a": norm(next(keys), n, D, dk),
        "g_b": norm(next(keys), n, dk, Hk, dk),
        "o_norm": jnp.ones((n, dk), pdt),
    }


# KDA: whatever has a head axis shards by head; the low-rank maps' first
# halves and the head norm's one weight are every head's
_BY_HEAD = P(None, None, AXIS_TENSOR, None)


def _kda_specs(c):
    return {
        **{name: _BY_HEAD for name in ("wq", "wk", "wv", "conv_q", "conv_k",
                                       "conv_v", "f_b", "g_b")},
        "dt_bias": P(None, AXIS_TENSOR, None),
        "A_log": P(None, AXIS_TENSOR),
        "w_beta": P(None, None, AXIS_TENSOR),
    }


def _kda_check(c):
    return None, (
        ("'kda' beside attention that is not latent (kv_latent)",
         c.kv_latent is None),
        *_linear_rows(c, "KDA", same_heads=True))


def _kda_mixer(h, w, c, ctx: Ctx | None = None):
    """A KDA layer's mixer up to (not with) the output projection, from
    the normed input ``h`` [B, T, D] and the layer's own leaves ``w`` ->
    (o [B, T, H, dv], {the most negative cumulative log-decay inside any
    chunk}). ``attn_qkv`` the three projections, ``attn_core`` the chunked
    delta rule and nothing else; convolutions, gates and the gated head
    norm open their scopes in ``ops/linear_attention.py``. **From the
    projections to the head norm every array is FLAT**, [B, T, H * d], a
    head a 128-lane slice with 8 tokens in a tile's sublanes, as the
    rule's kernels read q, k, v, ``g`` and write ``o``: the projections
    are plain matmuls against ``wq`` / ``wk`` / ``wv`` viewed [D, H * d]
    (the leaves and their sharding by head keep their shapes; viewed FIRST
    and cast after, which is the order in which XLA takes the weights'
    gradient from the flat ``dq`` with no transposed copy of it), the
    convolution chains take and return flat arrays and round once, at
    their end, and ``g`` [B, T, H * dk] float32 goes from the gates to the
    rule as it is. An array of that size that changes its tiling costs a
    pass over HBM each way, 44 of them a step before PR 43 and 48 more
    before PR 49 (``ops/linear_attention.py``'s docstring;
    ``tests/test_kda_layout.py`` holds the compiled mixer to none)."""
    dt = c.compute_dtype
    with jax.named_scope("attn_qkv"):
        q, k, v = (jnp.einsum("btd,dc->btc", h,
                              w[name].reshape(c.d_model, -1).astype(dt))
                   for name in ("wq", "wk", "wv"))
    q, k, v = linear_attention.conv_silu(q, k, v, w["conv_q"], w["conv_k"],
                                         w["conv_v"])
    g, beta = linear_attention.gates(h, w)
    with jax.named_scope("attn_core"):
        o = linear_attention.gated_delta_rule(q, k, v, g, beta)
    return (linear_attention.gated_head_norm(o, h, w, eps=c.norm_eps),
            {LOG_DECAY_MIN.key: linear_attention.log_decay_min(g)})


def _gdn_init(c, keys, n, draw):
    """Gated DeltaNet's one published input projection is the leaves
    ``wq``, ``wk`` [D, key heads, dk], ``wv``, ``wz`` [D, heads, dv] (its
    columns regrouped by what they make: each shards by head), ``w_a`` and
    ``w_beta`` its second; the rest as ``_kda_init``, a head."""
    norm, uniform, pdt = draw.norm, draw.uniform, jnp.dtype(c.param_dtype)
    D, Hv, Hk = c.d_model, c.kda_heads, c.linear_key_heads or c.kda_heads
    d, taps = c.kda_head_dim, c.kda_conv
    edge = 1.0 / math.sqrt(taps)
    heads = {"q": Hk, "k": Hk, "v": Hv, "z": Hv}
    step = jnp.exp(uniform(next(keys), n, Hv, low=math.log(1e-3),
                           high=math.log(1e-1)))
    return {
        **{f"w{x}": norm(next(keys), n, D, heads[x], d) for x in "qkvz"},
        **{f"conv_{x}": uniform(next(keys), n, taps, heads[x], d,
                                low=-edge, high=edge).astype(pdt)
           for x in "qkv"},
        "w_a": norm(next(keys), n, D, Hv),
        "w_beta": norm(next(keys), n, D, Hv),
        "dt_bias": _step_bias(step, pdt),
        "A_log": jnp.log(uniform(next(keys), n, Hv, low=1.0,
                                 high=16.0)).astype(pdt),
        "o_norm": jnp.ones((n, d), pdt),
    }


def _gdn_specs(c):
    # the same rule as KDA's (q and k by KEY head)
    return {
        **{name: _BY_HEAD for name in ("wq", "wk", "wv", "wz", "conv_q",
                                       "conv_k", "conv_v")},
        "dt_bias": P(None, AXIS_TENSOR), "A_log": P(None, AXIS_TENSOR),
        "w_a": P(None, None, AXIS_TENSOR),
        "w_beta": P(None, None, AXIS_TENSOR),
    }


def _gdn_check(c):
    return None, (
        ("'gdn' beside latent attention (kv_latent)",
         c.kv_latent is not None),
        *_linear_rows(c, "Gated DeltaNet"))


def _gdn_mixer(h, w, c, ctx: Ctx | None = None):
    """A Gated DeltaNet layer's mixer up to (not with) the output
    projection, from the normed input ``h`` [B, T, D] and the layer's own
    leaves ``w`` -> (o [B, T, H, dv], {the most negative cumulative
    log-decay inside any chunk}). As ``_kda_mixer``, FLAT from the
    projections to the head norm; what differs is the mechanism: the
    published ONE input projection (q, k by KEY head, v and the gate's z
    by value head: four plain matmuls under ``attn_qkv``), ONE log-decay a
    value head and ``beta`` from a second, 2 x heads wide (``head_gates``),
    a key head read by ``kda_heads / linear_key_heads`` value heads (q and
    k go to the rule as VIEWS by heads, which is how it learns their
    count: nothing is computed on the view), and the output gate
    ``silu(z)`` (``silu_gated_head_norm``)."""
    dt = c.compute_dtype
    with jax.named_scope("attn_qkv"):
        q, k, v, z = (jnp.einsum("btd,dc->btc", h,
                                 w[name].reshape(c.d_model, -1).astype(dt))
                      for name in ("wq", "wk", "wv", "wz"))
    q, k, v = linear_attention.conv_silu(q, k, v, w["conv_q"], w["conv_k"],
                                         w["conv_v"])
    g, beta = linear_attention.head_gates(h, w)
    with jax.named_scope("attn_core"):
        q, k = (a.reshape(*a.shape[:2], -1, c.kda_head_dim) for a in (q, k))
        o = linear_attention.gated_delta_rule(q, k, v, g, beta)
    return (linear_attention.silu_gated_head_norm(o, z, w["o_norm"],
                                                  eps=c.norm_eps),
            {LOG_DECAY_MIN.key: linear_attention.log_decay_min(g)})


def _ssm_init(c, keys, n, draw):
    """A state-space layer's leaves: the published ONE input projection as
    ``w_z`` [D, heads, channels] (the gate), ``w_xbc`` [D, heads x channels
    + 2 x groups x state] (``[x | B | C]``, one convolution's operand) and
    ``w_dt`` [D, heads]; ``conv_w`` [taps, .] and ``conv_b``
    (``ssm_conv_bias``) U(-1 / sqrt(taps), 1 / sqrt(taps)); ``dt_bias`` and
    ``A_log`` as ``_kda_init``'s, a head; ``D`` 1; ``o_norm`` [heads x
    channels] 1."""
    norm, uniform, pdt = draw.norm, draw.uniform, jnp.dtype(c.param_dtype)
    D, Hs, P_, taps = c.d_model, c.kda_heads, c.kda_head_dim, c.kda_conv
    wide = Hs * P_ + 2 * c.ssm_groups * c.ssm_state
    edge = 1.0 / math.sqrt(taps)
    step = jnp.maximum(jnp.exp(uniform(
        next(keys), n, Hs, low=math.log(1e-3), high=math.log(1e-1))),
        1e-4)
    return {
        "w_z": norm(next(keys), n, D, Hs, P_),
        "w_xbc": norm(next(keys), n, D, wide),
        "w_dt": norm(next(keys), n, D, Hs),
        "conv_w": uniform(next(keys), n, taps, wide, low=-edge,
                          high=edge).astype(pdt),
        **({"conv_b": uniform(next(keys), n, wide, low=-edge,
                              high=edge).astype(pdt)}
           if c.ssm_conv_bias else {}),
        "dt_bias": _step_bias(step, pdt),
        "A_log": jnp.log(uniform(next(keys), n, Hs, low=1.0,
                                 high=16.0)).astype(pdt),
        "D": jnp.ones((n, Hs), pdt),
        "o_norm": jnp.ones((n, Hs * P_), pdt),
    }


def _ssm_specs(c):
    # the gate's projection by head; what the ONE convolution reads side
    # by side ([x | B | C]) and the rest replicated (the scan does not run
    # under a mesh's tensor axis yet: ROADMAP B3)
    return {"w_z": _BY_HEAD}


def _ssm_check(c):
    return (("ssm_state >= 1, ssm_chunk >= 1 and ssm_groups "
             "that divide kda_heads",
             min(c.ssm_state, c.ssm_chunk, c.ssm_groups) >= 1
             and c.kda_heads % max(c.ssm_groups, 1) == 0),
            (("'ssm' beside latent attention (kv_latent)",
              c.kv_latent is not None),
             *_linear_rows(c, "state-space")))


def _ssm_mixer(h, w, c, ctx: Ctx | None = None):
    """A state-space (Mamba-2) layer's mixer up to (not with) the output
    projection, from the normed input ``h`` [B, T, D] and the layer's own
    leaves ``w`` -> (o FLAT [B, T, heads x channels], {the most negative
    cumulative log-decay inside any chunk, the mean step ``Delta``}). Its
    parts under the names the linear mixers' readers read: ``attn_qkv``
    the published ONE input projection as three plain matmuls (the gate's
    ``z``, ``[x | B | C]`` side by side as the ONE convolution reads them,
    the step's ``dt`` summed in float32), ``kda_conv`` the chain on ``[x |
    B | C]``, ``kda_gate`` the step, the decay, the gated group norm and
    the counters, ``attn_core`` the ONE scan call and nothing else
    (``ops/state_space.py``). On one TPU chip chain, scan and norm are
    Pallas passes over the same flat tiling (each op's own rule, all three
    asking ``linear_attention._one_tpu``); elsewhere their plain forms."""
    dt = c.compute_dtype
    heads, width, groups = c.kda_heads, c.kda_head_dim, c.ssm_groups
    with jax.named_scope("attn_qkv"):
        z, xbc = (jnp.einsum("btd,dc->btc", h,
                             w[name].reshape(c.d_model, -1).astype(dt))
                  for name in ("w_z", "w_xbc"))
        raw = jnp.einsum("btd,dh->bth", h, w["w_dt"].astype(dt),
                         preferred_element_type=jnp.float32)
    xbc = linear_attention.flat_conv_silu(
        xbc, w["conv_w"], w["conv_b"] if c.ssm_conv_bias else None)
    step, decay = state_space.step_and_decay(raw, w)
    with jax.named_scope("attn_core"):
        inner, state = heads * width, groups * c.ssm_state
        by = lambda a, n: a.reshape(*a.shape[:2], n, -1)
        o = state_space.ssm_scan(
            by(xbc[..., :inner], heads), step, decay,
            by(xbc[..., inner:inner + state], groups),
            by(xbc[..., inner + state:], groups), w["D"], chunk=c.ssm_chunk)
    o = state_space.gated_group_norm(o, z, w["o_norm"], groups,
                                     eps=c.norm_eps)
    with jax.named_scope("kda_gate"):
        step_mean = jax.lax.stop_gradient(step).mean()
    return o, {
        LOG_DECAY_MIN.key: linear_attention.log_decay_min(decay, c.ssm_chunk),
        STEP_MEAN.key: step_mean}


# -- Mamba-1, and the readers of a memory ------------------------------------------

def refuses_shared_memory(c) -> str:
    return (
        f"KV-cache decode does not run a model with layer_mixers "
        f"({c.layer_mixers!r}; ssm_expand {c.ssm_expand}, ssm_state "
        f"{c.ssm_state}, ssm_dt_rank {c.ssm_dt_rank}, kda_conv {c.kda_conv}, "
        f"diff_attn {c.diff_attn}): a selective-scan ('ssm1') layer keeps "
        f"a recurrent state [channels, ssm_state] and its convolution's "
        f"last positions, not keys and values; differential attention "
        f"reads TWO softmax maps a pair of heads and norms their "
        f"difference; a gated memory unit ('gmu') reads the scan output of "
        f"an EARLIER layer, which no cache holds; and the 'cross' layers "
        f"read ONE earlier layer's keys and values, where the cache would "
        f"give each layer its own")


def _rounded(a):
    """``a`` in float32, holding the values ``a``'s own dtype rounded it to:
    left to itself XLA may hand a consumer the float32 a matmul or a sum
    made BEFORE the rounding (excess precision), and a forward alone and
    the forward of a train step then differ (``_latent_qkv`` has the case
    that showed it)."""
    bits = jnp.finfo(a.dtype)
    return jax.lax.reduce_precision(a.astype(jnp.float32), bits.nexp,
                                    bits.nmant)


def _ssm1_init(c, keys, n, draw):
    """A Mamba-1 layer's leaves, the implementation's init (``mamba_ssm``
    ``Mamba``): the published ONE input projection as ``w_x`` and ``w_z``
    [D, inner] (its columns by what they make), ``conv_w`` [taps, inner]
    and ``conv_b`` (``ssm_conv_bias``) U(-1 / sqrt(taps), 1 / sqrt(taps)),
    ``w_low`` [inner, rank + 2 x state] (``[dt_low | B | C]``), ``w_dt``
    [rank, inner] U(-rank^-0.5, rank^-0.5), ``dt_bias`` the inverse softplus
    of a step log-uniform in [0.001, 0.1] floored at 1e-4, ``A_log`` =
    log(1..state) a channel, ``D`` 1, ``wo`` [inner, D]."""
    norm, uniform, pdt = draw.norm, draw.uniform, jnp.dtype(c.param_dtype)
    D, inner, state, rank = c.d_model, c.ssm_inner, c.ssm_state, c.ssm_dt_rank
    edge, dt_edge = 1.0 / math.sqrt(c.kda_conv), rank ** -0.5
    step = jnp.maximum(jnp.exp(uniform(
        next(keys), n, inner, low=math.log(1e-3), high=math.log(1e-1))),
        1e-4)
    return {
        "w_x": norm(next(keys), n, D, inner),
        "w_z": norm(next(keys), n, D, inner),
        "conv_w": uniform(next(keys), n, c.kda_conv, inner, low=-edge,
                          high=edge).astype(pdt),
        **({"conv_b": uniform(next(keys), n, inner, low=-edge,
                              high=edge).astype(pdt)}
           if c.ssm_conv_bias else {}),
        "w_low": norm(next(keys), n, inner, rank + 2 * state),
        "w_dt": uniform(next(keys), n, rank, inner, low=-dt_edge,
                        high=dt_edge).astype(pdt),
        "dt_bias": _step_bias(step, pdt),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, state + 1, dtype=jnp.float32)),
            (n, inner, state)).astype(pdt),
        "D": jnp.ones((n, inner), pdt),
        "wo": norm(next(keys), n, inner, D, s=draw.res_std),
    }


def _ssm1_check(c):
    return (("ssm_expand, ssm_state, ssm_dt_rank, ssm_chunk and kda_conv "
             ">= 1", min(c.ssm_expand, c.ssm_state, c.ssm_dt_rank,
                         c.ssm_chunk, c.kda_conv) >= 1),
            (("'ssm1' beside latent attention (kv_latent)",
              c.kv_latent is not None),))


def _ssm1_mixer(h, w, c, ctx: Ctx | None = None):
    """A Mamba-1 (selective-scan, arXiv:2312.00752) layer's mixer WITH its
    output projection, from the normed input ``h`` [B, T, D] and the
    layer's own leaves ``w`` -> (out [B, T, D], its counters, {"m": the
    scan's output ``y`` [B, T, inner] with the ``D`` term, AHEAD of the
    gate}). ``[x | z] = h W_in``; ``x' = silu(conv(x) + b)``; ``[dt_low |
    B_t | C_t] = x' W_low``; ``Delta_t = softplus(dt_low W_dt + b_dt)``, ONE
    step a channel; ``A = -exp(A_log)`` [inner, state]; ``y`` the recurrence
    (``state_space.selective_scan``: the decay differs by channel AND state
    index, ``B_t`` and ``C_t`` are every channel's); ``out = W_o (y *
    silu(z))``. No norm inside. Its parts under the names the linear
    mixers' readers read: ``attn_qkv`` the three projections, ``kda_conv``
    the chain (``flat_conv_silu``), ``kda_gate`` the step, the gate and the
    counters, ``attn_core`` the ONE scan call, ``attn_out`` ``W_o``."""
    dt = c.compute_dtype
    rank, state = c.ssm_dt_rank, c.ssm_state
    with jax.named_scope("attn_qkv"):
        x, z = (jnp.einsum("btd,dc->btc", h, w[name].astype(dt))
                for name in ("w_x", "w_z"))
    x = linear_attention.flat_conv_silu(
        x, w["conv_w"], w["conv_b"] if c.ssm_conv_bias else None)
    with jax.named_scope("attn_qkv"):
        low = jnp.einsum("btc,cr->btr", x, w["w_low"].astype(dt))
        raw = jnp.einsum("btr,rc->btc", low[..., :rank], w["w_dt"].astype(dt),
                         preferred_element_type=jnp.float32)
    step, a = state_space.step_and_decay(raw, w)
    with jax.named_scope("attn_core"):
        y = state_space.selective_scan(
            x, step, a, low[..., rank:rank + state], low[..., rank + state:],
            w["D"], chunk=c.ssm_chunk)
    with jax.named_scope("kda_gate"):
        gated = (_rounded(y) * jax.nn.silu(_rounded(z))).astype(dt)
        step = jax.lax.stop_gradient(step)
        # the most negative ``Delta A`` summed inside a chunk: a channel's
        # summed step times its most negative ``A``
        counters = {
            LOG_DECAY_MIN.key: linear_attention.log_decay_min(
                step * jnp.min(a, axis=-1), c.ssm_chunk),
            STEP_MEAN.key: step.mean()}
    with jax.named_scope("attn_out"):
        out = jnp.einsum("btc,cd->btd", gated, w["wo"].astype(dt))
    return out, counters, {"m": y}


def _gmu_init(c, keys, n, draw):
    """A gated memory unit's two projections, ``w1`` [D, inner] and ``w2``
    [inner, D] (a residual-out projection), no bias."""
    return {"w1": draw.norm(next(keys), n, c.d_model, c.ssm_inner),
            "w2": draw.norm(next(keys), n, c.ssm_inner, c.d_model,
                            s=draw.res_std)}


def _gmu_mixer(h, w, c, ctx: Ctx):
    """A gated memory unit (SambaY, arXiv:2507.06607) WITH its output
    projection: ``W_2 (m * silu(h W_1))``, ``m`` the scan output an earlier
    ``"ssm1"`` layer handed out (``ctx.memory``). No scan, no convolution,
    no state of its own; all of it under ``gmu``."""
    dt = c.compute_dtype
    with jax.named_scope(GMU_SCOPE):
        gate = jax.nn.silu(jnp.einsum(
            "btd,dc->btc", h, w["w1"].astype(dt),
            preferred_element_type=jnp.float32))
        gated = (_rounded(ctx.memory["m"]) * gate).astype(dt)
        out = jnp.einsum("btc,cd->btd", gated, w["w2"].astype(dt))
        return out, {GMU_GATE_MEAN.key: jax.lax.stop_gradient(gate).mean()}


def _cross_init(c, keys, n, draw):
    """A cross layer's own leaves: the query projection ``wq`` (``bq``) and
    the differential form's (``_diff_leaves``); ``attn/wo`` (``bo``) is the
    stack's, as attention's."""
    first = next(keys)
    more = iter(jax.random.split(jax.random.fold_in(first, 1), 8))
    return {"wq": draw.norm(first, n, c.d_model, c.n_heads, c.head_dim),
            **({"bq": draw.norm(next(more), n, c.n_heads, c.head_dim)}
               if c.attn_bias else {}),
            **_diff_leaves(c, more, n, draw)}


def _cross_attention(h, w, c, ctx: Ctx):
    """Differential CROSS-attention up to (not with) the output projection:
    the layer's own queries against the keys and values an earlier
    attention layer handed out (``ctx.memory``), full causal, its own
    ``lambda``s and pair norm; all of it under ``attn_cross``."""
    with jax.named_scope(CROSS_SCOPE):
        return _diff_core(*_diff_queries(h, w, c), *ctx.memory["kv"], w, c,
                          ctx)


# -- the records --------------------------------------------------------------------

def _linear(name: str, init, specs, check, apply, counters, decodes,
            **more) -> Mixer:
    """A linear mixer's record: its own leaves under its own name, its
    work under ``attn_linear``."""
    return Mixer(stack=lambda c: name, scope=lambda window: ATTN_SCOPES[2],
                 init=init, specs=specs, check=check, apply=apply,
                 counters=lambda c: counters, decodes=decodes, **more)


MIXERS = {
    "attn": Mixer(
        # beside other mixers a stack of its own, ``mla`` (latent) or
        # ``mha`` (plain: q, k, v, the head norms, the gate)
        stack=lambda c: ("attn" if not c.layer_mixers
                         else "mha" if c.kv_latent is None else "mla"),
        scope=lambda window: ATTN_SCOPES[window is not None],
        init=_attn_init, specs=_attn_specs, check=lambda c: (None, ()),
        apply=_attention,
        counters=lambda c: ((GATE_MEAN,) if c.attn_gate else ())
        + ((DIFF_LAMBDA,) if c.diff_attn else ()),
        decodes=None, writes="kv"),
    "kda": _linear("kda", _kda_init, _kda_specs, _kda_check, _kda_mixer,
                   (LOG_DECAY_MIN,), refuses_linear),
    "gdn": _linear("gdn", _gdn_init, _gdn_specs, _gdn_check, _gdn_mixer,
                   (LOG_DECAY_MIN,), refuses_linear),
    "ssm": _linear("ssm", _ssm_init, _ssm_specs, _ssm_check, _ssm_mixer,
                   (LOG_DECAY_MIN, STEP_MEAN), refuses_single_sublayers),
    # replicated under a mesh, as "ssm"'s (ROADMAP B3)
    "ssm1": _linear("ssm1", _ssm1_init, lambda c: {}, _ssm1_check,
                    _ssm1_mixer, (LOG_DECAY_MIN, STEP_MEAN),
                    refuses_shared_memory, own_out=True, writes="m"),
    "gmu": _linear("gmu", _gmu_init, lambda c: {},
                   lambda c: (("ssm_expand >= 1 (the memory's width)",
                               c.ssm_expand >= 1), ()),
                   _gmu_mixer, (GMU_GATE_MEAN,), refuses_shared_memory,
                   own_out=True, reads="m"),
    "cross": Mixer(
        stack=lambda c: "cross", scope=lambda window: ATTN_SCOPES[0],
        init=_cross_init, specs=lambda c: {"wq": _BY_HEAD},
        check=lambda c: (("diff_attn (cross-attention is written in its "
                          "differential form alone)", c.diff_attn), ()),
        apply=_cross_attention, counters=lambda c: (DIFF_LAMBDA,),
        decodes=refuses_shared_memory, reads="kv"),
}
