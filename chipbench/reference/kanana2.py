"""Plain reference of the ``kanana2`` arch (kanana-2-30b-a3b,
``kakaocorp/kanana-2-30b-a3b-instruct-2601``'s ``config.json``,
``model_type`` ``deepseek_v3``; the layer is DeepSeek-V3's,
arXiv:2412.19437). No bias on any projection, RMSNorm eps 1e-6, pre-norm
residual blocks, untied head. Layer 0 is DENSE, every later one an EXPERT
layer (``first_k_dense_replace`` 1, ``moe_layer_freq`` 1). With ``h`` the
residual stream, D = 2048:

    x = RMSNorm1(h)
    q = Wq x                                32 heads x 192 = [q_nope (128) ; q_rope (64)]
    [c ; k_r] = Wkva x                      512 + 64; no query latent (q_lora_rank null)
    [k_nope_i ; v_i] = Wkvb_i RMSNorm512(c) 128 + 128 for each head i
    score_i = (q_nope_i . k_nope_i + rope(q_rope_i) . rope(k_r)) / sqrt(192)
                                            ONE k_r a token, every head's; theta 1e6
    h = h + Wo [softmax_causal(score_i) v_i for the 32 heads]
    u = RMSNorm2(h)
    dense layer:   h = h + SwiGLU_6144(u)
    expert layer:  s = sigmoid(Wr u)        128 scores, float32
                   S = top6(s + b)          b enters the CHOICE and nothing else
                   g = 2.448 x s[S] / sum(s[S])
                   h = h + sum over e in S of g_e SwiGLU_768,e(u) + SwiGLU_1536(u)
                                            the last: the two shared experts, fused, ungated

then a final RMSNorm and the head. ``n_group`` = ``topk_group`` = 1: no
group limit on the choice. RoPE pairs the HALVES of the 64 rotary columns
(the program's ``apply_rope``); ``rope_interleave`` true pairs neighbours,
which with seeded weights is a fixed permutation of 64 columns of ``Wq``
and ``Wkva``, the same on both sides (the config file's ``assumed``).

THE SHARE. The parameters hold ``H``, 16 consecutive experts of the 128
(``cfg.experts_held`` = (rank, of)). The router, the top-6 and the gates
stay over all 128; the sum runs over ``S`` intersected with ``H`` only,
and the shared expert is added whole. What the absent experts would add
is left out, and that partial ``h`` is what the next layer reads: one
expert-parallel rank without its exchange. With ``experts_held`` None it
IS the whole model.

The training loss is the next-token cross entropy and nothing else
(``aux_loss_alpha`` has no part in the bias-balanced recipe): this module
exports no ``loss``. The bias ``b`` is moved by the train step's rule and
by no gradient; here it is read as it stands.

float32 throughout under ``default_matmul_precision("highest")``; no
kernel, no sort, nothing of ``ray_tpu/ops/``: attention through
``_common.causal_attention`` in query blocks, the rotary key repeated to
the heads; a token meets its experts through a [tokens, 128] matrix of
gates that is zero where the expert was not chosen, in a loop over the
HELD experts. One layer at a time over the program's stacked weights.

Departures from the published model: rows are seeded tokens (no segment
mask); weights are seeded N(0, 0.02), ``b`` too, not the checkpoint's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common
from chipbench.reference.llama import _rope

EPS = 1e-6                # rms_norm_eps


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _attention(x, w, nope: int, latent: int, theta: float):
    """Latent attention's output projection of ``x`` [B, T, D]."""
    heads = w["wq"].shape[1]
    q = jnp.einsum("btd,dhk->bthk", x, w["wq"])
    down = x @ w["wkv_a"]                                       # [B, T, 576]
    kv = jnp.einsum("btc,chk->bthk", _rms(down[..., :latent], w["kv_norm"]),
                    w["wkv_b"])
    k_r = _rope(down[:, :, None, latent:], theta)               # one a token
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_r, heads, axis=2)], -1)
    o = _common.causal_attention(q, k, kv[..., nope:])
    return jnp.einsum("bthk,hkd->btd", o, w["wo"])


def _experts(u, gates, mlp):
    """u [N, D], gates [N, Eh] (zero where not chosen) -> [N, D]."""
    def one_expert(out, expert):
        gate_e, w_gate, w_up, w_down = expert
        return out + gate_e[:, None] * _swiglu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          (gates.T, mlp["w_gate"], mlp["w_up"],
                           mlp["w_down"]))
    return out


def _layer(h, lp, dense: bool, nope: int, latent: int, theta: float,
           top_k: int, gate_scale: float, first_held: int):
    B, T, D = h.shape
    h = h + _attention(_rms(h, lp["ln1"]["w"]), lp["attn"], nope, latent,
                       theta)
    u = _rms(h, lp["ln2"]["w"]).reshape(B * T, D)
    mlp = lp["mlp"]
    if dense:
        out = _swiglu(u, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
        return h + out.reshape(B, T, D)
    s = jax.nn.sigmoid(u @ lp["router"]["w"])                   # [N, 128]
    _, chosen = jax.lax.top_k(s + lp["router"]["b"], top_k)
    picked = jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32).sum(1)
    gates = gate_scale * picked * s / (picked * s).sum(-1, keepdims=True)
    held = mlp["w_gate"].shape[0]
    out = _experts(u, gates[:, first_held:first_held + held], mlp)
    out = out + _swiglu(u, mlp["shared_w_gate"], mlp["shared_w_up"],
                        mlp["shared_w_down"])
    return h + out.reshape(B, T, D)


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6, 7, 8))
    rank, of = cfg.experts_held or (0, 1)
    static = (cfg.d_head_nope, cfg.kv_latent, float(cfg.rope_theta),
              cfg.expert_top_k, float(cfg.expert_gate_scale),
              rank * (cfg.n_experts // of))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        for i in range(cfg.n_dense_layers):
            x = layer(x, _common.layer_slice(params["dense_layers"], i),
                      True, *static)
        for i in range(cfg.n_layers - cfg.n_dense_layers):
            x = layer(x, _common.layer_slice(params["layers"], i), False,
                      *static)
        x = _rms(x, params["final_norm"]["w"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32)
