"""Plain reference of the ``qwen3_next`` arch (Qwen3-Next-80B-A3B,
``Qwen/Qwen3-Next-80B-A3B-Instruct``'s ``config.json``; what that file has
no key for is the public implementation's, ``transformers``
``models/qwen3_next/modeling_qwen3_next.py``). No bias on any projection,
eps 1e-6, pre-norm residual blocks, untied head. Layer ``i`` (from 0) is
ATTENTION where ``(i + 1) % 4 == 0``, else GATED DELTANET; every layer
has experts. ``norm0(x; w) = x / sqrt(mean x^2 + eps) x (1 + w)`` is the
zero-centred RMSNorm of both block norms, the final norm and the q / k
head norms; ``norm(x; w)`` the plain one, DeltaNet's output norm. With
``h = norm0(x)`` of the residual stream ``x``, D = 2048:

Attention layer, 16 query heads on 2 key / value heads of 256:

    [q | gate] = Wq h, Wg h          k, v = Wk h, Wv h
    q, k = norm0_head(q), norm0_head(k)          one 256-wide weight each
    q, k = rope64(q), rope64(k)      theta 1e7 on the FIRST 64 of a head's 256
                                     values, their two halves (32 + 32) paired;
                                     the other 192 as they are
    a = softmax_causal(q k^T / sqrt(256)) v      query head n on key head n // 8
    x = x + Wo [a * sigmoid(gate)]

Gated DeltaNet layer, 16 query / key heads under 32 value heads of 128:

    q, k, v = silu(conv4(Wq h)), silu(conv4(Wk h)), silu(conv4(Wv h))
                                     conv4: causal, depthwise, the token and
                                     the three before it, no bias
    q, k = l2norm_head(q), l2norm_head(k)        value head j reads key head j // 2
    beta_t = sigmoid(Wb h_t)         g_t = -exp(A_log) * softplus(Wa h_t + dt_bias)
                                     ONE number a value head and token each
    S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T     S_0 = 0
    o_t = S_t^T q_t / sqrt(128)
    x = x + Wo [ norm_head(o_t; w in R^128, every head's) * silu(Wz h_t) ]

The state is carried TOKEN BY TOKEN (``delta_rule``: one ``lax.scan`` step
a position, no chunks, the decay a scalar a head), so nothing here shares
a form with ``ray_tpu/ops/linear_attention.py``.

Expert FFN of every layer, ``u = norm0(x)``: ``p = softmax(Wr u)`` over
512 in float32, ``S = top10(p)``, ``g = p[S] / sum(p[S])``,

    x = x + sum over e in S of g_e SwiGLU_512,e(u) + sigmoid(w_s . u) SwiGLU_512,shared(u)

Then the final norm0 and the head.

THE SHARE. The parameters hold ``H``, consecutive experts of the 512
(``cfg.experts_held`` = (rank, of)); router, top-10 and gates stay over
all 512 and the sum runs over ``S`` intersected with ``H``; what the
absent experts would add is left out, and that partial ``x`` is what the
next layer reads. The gated shared expert is added whole. With
``experts_held`` None it IS the whole model.

The training loss (``loss``) is the next-token cross entropy + 0.001 x the
load-balance term, the mean over the layers of ``512 x sum_e f_e P_e``
over ALL 512 experts (``f_e`` the share of the assignments expert ``e``
got, ``P_e`` its mean probability).

float32 throughout under ``default_matmul_precision("highest")``; nothing
of ``ray_tpu/ops/``. Scores are materialised a block of queries at a time
(``afmoe._attention``); a token meets its experts through a [tokens, held]
matrix of gates that is zero where the expert was not chosen, in a loop
over the HELD experts. One layer at a time over the program's stacks:
the leaves every layer has (``ln1``, ``ln2``, ``attn.wo``, router,
``mlp``) are stacked over all layers, a mixer's own leaves (``gdn.*``,
``mha.*``) over the layers of that kind; ``attn.wo`` [16, 256, D] is a
DeltaNet layer's [32 x 128, D] by rows.

Departures from the public implementation: rows are seeded tokens (no
segment mask, no padding); weights are seeded, not the checkpoint's; the
published input projections are fused (``in_proj_qkvz``, ``in_proj_ba``,
``q_proj`` with its gate) where the leaves here are one a result: the
same mathematics; no multi-token-prediction module.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common
from chipbench.reference.afmoe import _attention, _experts, _swiglu

EPS = 1e-6                # rms_norm_eps
L2_EPS = 1e-6
FULL_EVERY = 4            # full_attention_interval
BALANCE_WEIGHT = 0.001    # router_aux_loss_coef (Qwen3NextConfig's default)


def _norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _norm0(x, w):
    return _norm(x, 1.0 + w)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _rope_part(x, theta: float, rotated: int):
    """x [B, T, H, Dh]: the first ``rotated`` values of a head rotated by
    position, their two halves paired; the rest as they are."""
    half = rotated // 2
    inv = 1.0 / theta ** (jnp.arange(0, rotated, 2, dtype=jnp.float32)
                          / rotated)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b, rest = x[..., :half], x[..., half:rotated], x[..., rotated:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def conv4(x, w):
    """Causal depthwise convolution: x [B, T, H, d], w [K, H, d]; the LAST
    tap multiplies the token itself."""
    taps, length = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j                         # positions behind
        out = out + w[j] * jnp.pad(
            x, ((0, 0), (back, 0), (0, 0), (0, 0)))[:, :length]
    return out


def delta_rule(q, k, v, g, beta):
    """The gated delta rule with ONE decay a head, one position a step.
    q, k [B, T, H, dk], v [B, T, H, dv], g (log-decay) and beta [B, T, H]
    -> o [B, T, H, dv]; the state [B, H, dk, dv] starts at 0."""
    scale = q.shape[-1] ** -0.5

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        seen = jnp.einsum("bhc,bhce->bhe", k_t, state)          # S^T k
        state = state + jnp.einsum(
            "bhc,bhe->bhce", k_t, b_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhc,bhce->bhe", q_t, state) * scale

    state = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]),
                      jnp.float32)
    _, o = jax.lax.scan(token, state, jax.tree.map(
        lambda a: jnp.moveaxis(a, 1, 0), (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _deltanet(h, w):
    """The Gated DeltaNet mixer of ``h`` [B, T, D] up to (not with) Wo:
    [B, T, 32, 128]."""
    proj = lambda name: jnp.einsum("btd,dhk->bthk", h, w[name])
    q = _l2norm(jax.nn.silu(conv4(proj("wq"), w["conv_q"])))
    k = _l2norm(jax.nn.silu(conv4(proj("wk"), w["conv_k"])))
    v = jax.nn.silu(conv4(proj("wv"), w["conv_v"]))
    each = v.shape[2] // q.shape[2]            # value heads a key head
    q, k = jnp.repeat(q, each, axis=2), jnp.repeat(k, each, axis=2)
    beta = jax.nn.sigmoid(h @ w["w_beta"])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(h @ w["w_a"] + w["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    return _norm(o, w["o_norm"]) * jax.nn.silu(proj("wz"))


def _gated_attention(h, w, theta: float, rotated: int):
    """The attention mixer of ``h`` up to (not with) Wo: [B, T, 16, 256]."""
    proj = lambda name: jnp.einsum("btd,dhk->bthk", h, w[name])
    q = _rope_part(_norm0(proj("wq"), w["q_norm"]), theta, rotated)
    k = _rope_part(_norm0(proj("wk"), w["k_norm"]), theta, rotated)
    a = _attention(q, k, proj("wv"), None)
    return a * jax.nn.sigmoid(proj("wg"))


def _layer(x, lp, mixer: str, theta: float, rotated: int, top_k: int,
           first_held: int):
    """One block on x [B, T, D] -> (x, the layer's balance term)."""
    B, T, D = x.shape
    h = _norm0(x, lp["ln1"]["w"])
    o = (_deltanet(h, lp["gdn"]) if mixer == "gdn"
         else _gated_attention(h, lp["mha"], theta, rotated))
    x = x + o.reshape(B, T, -1) @ lp["attn"]["wo"].reshape(-1, D)
    u = _norm0(x, lp["ln2"]["w"]).reshape(B * T, D)
    mlp = lp["mlp"]
    p = jax.nn.softmax(u @ lp["router"]["w"], axis=-1)           # [N, 512]
    top_p, top_e = jax.lax.top_k(p, top_k)
    chosen = jax.nn.one_hot(top_e, p.shape[-1], dtype=jnp.float32)
    gates = (chosen * (top_p / top_p.sum(-1, keepdims=True))[..., None]
             ).sum(1)                                            # [N, 512]
    held = mlp["w_gate"].shape[0]
    out = _experts(u, gates[:, first_held:first_held + held], mlp)
    share = jax.nn.sigmoid(u @ mlp["shared_gate"])[:, None]
    out = out + share * _swiglu(u, mlp["shared_w_gate"], mlp["shared_w_up"],
                                mlp["shared_w_down"])
    f = chosen.sum((0, 1)) / (B * T * top_k)                     # sums to 1
    balance = p.shape[-1] * jnp.sum(f * p.mean(0))
    return x + out.reshape(B, T, D), balance


def stack_layer(stack, mixers, i: int):
    """Layer ``i`` of a stack whose layers have the mixers ``mixers``: the
    leaves every layer has at ``i``, its mixer's own leaves at its place
    among the layers of that kind."""
    own = {"gdn": "gdn", "attn": "mha"}[mixers[i]]
    place = list(mixers[:i]).count(mixers[i])
    lp = {name: _common.layer_slice(sub, i) for name, sub in stack.items()
          if name not in ("gdn", "mha")}
    lp[own] = _common.layer_slice(stack[own], place)
    return lp


_jit_layer = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6))


def _run(params, tokens, cfg):
    """(logits, the balance term as a mean over the layers)."""
    rank, of = cfg.experts_held or (0, 1)
    static = (float(cfg.rope_theta), int(cfg.head_dim * cfg.rope_fraction),
              cfg.expert_top_k, rank * (cfg.n_experts // of))
    mixers = ["attn" if (i + 1) % FULL_EVERY == 0 else "gdn"
              for i in range(cfg.n_layers)]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        balance = 0.0
        for i, mixer in enumerate(mixers):
            x, b_i = _jit_layer(x, stack_layer(params["layers"], mixers, i),
                                mixer, *static)
            balance = balance + b_i / cfg.n_layers
        x = _norm0(x, params["final_norm"]["w"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32), balance


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    return _run(params, tokens, cfg)[0]


def loss(params, tokens, cfg):
    """The whole training loss on rows ``tokens`` [B, T + 1]."""
    logits, balance = _run(params, tokens[:, :-1], cfg)
    return (_common.next_token_loss(logits, tokens)
            + BALANCE_WEIGHT * balance)
