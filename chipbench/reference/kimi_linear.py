"""Plain reference of the ``kimi_linear`` arch (Kimi-Linear-48B-A3B,
``moonshotai/Kimi-Linear-48B-A3B-Instruct``'s ``config.json``, ``model_type``
``kimi_linear``; Kimi Linear, arXiv:2510.26692). No bias on any
projection, RMSNorm eps 1e-5, pre-norm residual blocks, untied head, NO
positional encoding anywhere (``mla_use_nope``). Every layer is a token
mixer and an FFN; ``cfg.layer_mixers`` names each layer's mixer. With
``x = RMSNorm1(h)`` of the residual stream ``h``, D = 2304, 32 heads,
``dk = dv = 128``:

KDA layer (``"kda"``: gated delta-rule linear attention), per head:

    q = l2norm(silu(conv4(Wq x)))   k = l2norm(silu(conv4(Wk x)))   v = silu(conv4(Wv x))
                         conv4: causal, depthwise, the token and the three before it, no bias
    g_t = -exp(A_log) * softplus(Wf2 (Wf1 x_t) + dt_bias)      log-decay per CHANNEL, R^dk
    beta_t = sigmoid(Wb x_t)                                   a scalar a head
    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T      S_0 = 0, R^{dk x dv}
    o_t = S_t^T q_t / sqrt(dk)
    h = h + Wo [ rmsnorm_head(o_t) * sigmoid(Wg2 (Wg1 x_t)) ]  the norm's weight 128 wide, all heads'

The state is carried TOKEN BY TOKEN (``delta_rule``: one ``lax.scan``
step a position, no chunks), so nothing here shares a form with
``ray_tpu/ops/linear_attention.py``.

Latent-attention layer (``"attn"``): kanana-2's with the rotation left out,

    q = Wq x                                32 heads x 192 = [q_a (128) ; q_b (64)]
    [c ; k_b] = Wkva x                      512 + 64: ONE k_b a token, every head's
    [k_a_i ; v_i] = Wkvb_i RMSNorm512(c)    128 + 128 for each head i
    score_i = (q_a_i . k_a_i + q_b_i . k_b) / sqrt(192)
    h = h + Wo [softmax_causal(score_i) v_i for the 32 heads]

FFN, with ``u = RMSNorm2(h)``: layer 0 ``h + SwiGLU_9216(u)``; every later
layer ``s = sigmoid(Wr u)`` (256 scores, float32), ``S = top8(s + b)`` (the
bias enters the CHOICE and nothing else), ``g = 2.446 x s[S] / sum(s[S])``,
``h + sum over e in S of g_e SwiGLU_1024,e(u) + SwiGLU_1024(u)`` (the last:
the shared expert, ungated). Then a final RMSNorm and the head.

THE SHARE. The parameters hold ``H``, consecutive experts of the 256
(``cfg.experts_held`` = (rank, of)); router, top-8 and gates stay over
all 256 and the sum runs over ``S`` intersected with ``H``; what the
absent experts would add is left out, and that partial ``h`` is what the
next layer reads. With ``experts_held`` None it IS the whole model.

The training loss is the next-token cross entropy alone (the bias-
balanced recipe has no router term): this module exports no ``loss``.

float32 throughout under ``default_matmul_precision("highest")``; nothing
of ``ray_tpu/ops/``. Attention through ``_common.causal_attention`` in
query blocks (16,384 positions fit), the shared key part repeated to the
heads. One layer at a time over the program's stacks: the leaves every
layer has (``ln1``, ``ln2``, ``attn.wo``, router, ``mlp``) are stacked
over a stack's layers, a mixer's own leaves (``kda.*``, ``mla.*``) over
the layers of that kind.

Departures from the published model: rows are seeded tokens (no segment
mask); weights are seeded, not the checkpoint's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common
from chipbench.reference.kanana2 import _experts, _swiglu

L2_EPS = 1e-6


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def conv4(x, w):
    """Causal depthwise convolution: x [B, T, H, d], w [K, H, d]; the
    LAST tap multiplies the token itself."""
    taps, length = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j                         # positions behind
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :length - back]], axis=1)
        out = out + w[j] * shifted
    return out


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, one position a step. q, k [B, T, H, dk], v
    [B, T, H, dv], g [B, T, H, dk] (log-decay), beta [B, T, H] -> o [B, T,
    H, dv]; the state [B, H, dk, dv] starts at 0."""
    scale = q.shape[-1] ** -0.5

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state                 # diag(a) S
        seen = jnp.einsum("bhc,bhce->bhe", k_t, state)          # S^T k
        state = state + jnp.einsum(
            "bhc,bhe->bhce", k_t, b_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhc,bhce->bhe", q_t, state) * scale

    state = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]),
                      jnp.float32)
    _, o = jax.lax.scan(token, state, jax.tree.map(
        lambda a: jnp.moveaxis(a, 1, 0), (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _kda(x, w, eps):
    """The KDA mixer of ``x`` [B, T, D] up to (not with) ``Wo``."""
    proj = lambda name: jnp.einsum("btd,dhk->bthk", x, w[name])
    q = _l2norm(jax.nn.silu(conv4(proj("wq"), w["conv_q"])))
    k = _l2norm(jax.nn.silu(conv4(proj("wk"), w["conv_k"])))
    v = jax.nn.silu(conv4(proj("wv"), w["conv_v"]))
    f = jnp.einsum("btr,rhk->bthk", x @ w["f_a"], w["f_b"])
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(f + w["dt_bias"])
    beta = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", x, w["w_beta"]))
    o = delta_rule(q, k, v, g, beta)
    gate = jnp.einsum("btr,rhk->bthk", x @ w["g_a"], w["g_b"])
    return _rms(o, w["o_norm"], eps) * jax.nn.sigmoid(gate)


def _latent(x, w, nope: int, latent: int, eps):
    """NoPE latent attention of ``x`` up to (not with) ``Wo``."""
    heads = w["wq"].shape[1]
    q = jnp.einsum("btd,dhk->bthk", x, w["wq"])
    down = x @ w["wkv_a"]
    kv = jnp.einsum("btc,chk->bthk", _rms(down[..., :latent], w["kv_norm"],
                                          eps), w["wkv_b"])
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(
        down[:, :, None, latent:], heads, axis=2)], -1)
    return _common.causal_attention(q, k, kv[..., nope:])


def _layer(h, lp, mixer: str, dense: bool, nope: int, latent: int,
           eps: float, top_k: int, gate_scale: float, first_held: int):
    B, T, D = h.shape
    x = _rms(h, lp["ln1"]["w"], eps)
    o = (_kda(x, lp["kda"], eps) if mixer == "kda"
         else _latent(x, lp["mla"], nope, latent, eps))
    h = h + jnp.einsum("bthk,hkd->btd", o, lp["attn"]["wo"])
    u = _rms(h, lp["ln2"]["w"], eps).reshape(B * T, D)
    mlp = lp["mlp"]
    if dense:
        out = _swiglu(u, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
        return h + out.reshape(B, T, D)
    s = jax.nn.sigmoid(u @ lp["router"]["w"])                   # [N, 256]
    _, chosen = jax.lax.top_k(s + lp["router"]["b"], top_k)
    picked = jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32).sum(1)
    gates = gate_scale * picked * s / (picked * s).sum(-1, keepdims=True)
    held = mlp["w_gate"].shape[0]
    out = _experts(u, gates[:, first_held:first_held + held], mlp)
    out = out + _swiglu(u, mlp["shared_w_gate"], mlp["shared_w_up"],
                        mlp["shared_w_down"])
    return h + out.reshape(B, T, D)


def stack_layer(stack, mixers, i: int):
    """Layer ``i`` of a stack whose layers have the mixers ``mixers``:
    the leaves every layer has at ``i``, its mixer's own leaves at its
    place among the layers of that kind."""
    own = {"kda": "kda", "attn": "mla"}[mixers[i]]
    place = list(mixers[:i]).count(mixers[i])
    lp = {name: _common.layer_slice(sub, i) for name, sub in stack.items()
          if name not in ("kda", "mla")}
    lp[own] = _common.layer_slice(stack[own], place)
    return lp


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
    rank, of = cfg.experts_held or (0, 1)
    static = (cfg.d_head_nope, cfg.kv_latent, float(cfg.norm_eps),
              cfg.expert_top_k, float(cfg.expert_gate_scale),
              rank * (cfg.n_experts // of))
    n_dense = cfg.n_dense_layers
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        for name, mixers, dense in (
                ("dense_layers", cfg.layer_mixers[:n_dense], True),
                ("layers", cfg.layer_mixers[n_dense:], False)):
            for i, mixer in enumerate(mixers):
                x = layer(x, stack_layer(params[name], mixers, i), mixer,
                          dense, *static)
        x = _rms(x, params["final_norm"]["w"].astype(jnp.float32),
                 cfg.norm_eps)
        return x @ params["lm_head"].astype(jnp.float32)
