"""Plain reference of the ``olmoe`` arch (OLMoE-1B-7B, arXiv:2409.02060;
``allenai/OLMoE-1B-7B-0125-Instruct``'s ``config.json`` and the ``olmoe``
model code that reads it). One layer, no bias anywhere, RMSNorm eps 1e-5:

    a = RMSNorm(x)
    h = x + Wo . Attn(rope(qn(Wq a)), rope(kn(Wk a)), Wv a)
    u = RMSNorm(h)
    p = softmax(Wr u)                      float32, over the 64 experts
    y = h + sum over i in top8(p) of p_i . Wdown_i (silu(Wgate_i u) * Wup_i u)

``qn`` and ``kn`` are RMSNorm with a learned weight over the WHOLE
2048-wide projection (all heads together, not a head at a time), applied
before the split into 16 heads of 128 and before RoPE (rotate-half, theta
10000). Attention is causal softmax at scale 128^-0.5, 16 query heads on
16 key / value heads. The eight ``p_i`` are used as they are
(``norm_topk_prob: false``: no renormalisation). Every assignment is
computed: no capacity, no drop. Final RMSNorm, untied head.

Training loss (the recipe's, section 3 of the paper) =
cross entropy + 0.01 x balance + 0.001 x z, where a layer's
balance = 64 x sum_e f_e P_e (``f_e`` the share of the batch's (token,
choice) assignments that went to expert e, summing to 1; ``P_e`` the mean
of ``p_e``; both over all the tokens given) and a layer's
z = mean over tokens of logsumexp(Wr u)^2; both are means over layers.

float32 throughout under ``default_matmul_precision("highest")``; no
kernel, no sort, nothing of ``ray_tpu/ops/moe.py``: a token meets its
experts through a [tokens, experts] matrix of gates that is zero where
the expert was not chosen, in a loop over experts (every expert
multiplies every token of a block; the zero gates drop what was not
routed), a block of ``TOKEN_BLOCK`` tokens at a time, so that a row of
4096 fits beside a layer's 1.6 GB of float32 expert weights. One layer at
a time over the program's stacked weights.

Departures from the published model: rows are seeded tokens (no
documents, so no segment mask and no padding); weights are seeded
N(0, 0.02), not the checkpoint's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common
from chipbench.reference.llama import _rms, _rope

BALANCE_WEIGHT = 0.01     # the recipe's load-balancing loss weight
Z_WEIGHT = 0.001          # the recipe's router z-loss weight
TOKEN_BLOCK = 4096


def _experts(u, gates, mlp):
    """u [N, D], gates [N, E] (zero where not chosen) -> [N, D]."""
    def one_expert(out, expert):
        gate_e, w_gate, w_up, w_down = expert
        act = jax.nn.silu(u @ w_gate) * (u @ w_up)
        return out + gate_e[:, None] * (act @ w_down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          (gates.T, mlp["w_gate"], mlp["w_up"],
                           mlp["w_down"]))
    return out


def _attention(x, lp, n_heads: int, theta: float):
    """h = x + attention branch, on x [B, T, D]."""
    B, T, D = x.shape
    a = _rms(x, lp["ln1"]["w"])
    q = jnp.einsum("btd,dhk->bthk", a, lp["attn"]["wq"])
    k = jnp.einsum("btd,dhk->bthk", a, lp["attn"]["wk"])
    v = jnp.einsum("btd,dhk->bthk", a, lp["attn"]["wv"])
    # QK-norm over the full width: heads flattened, normed, split again
    q = _rms(q.reshape(B, T, -1), lp["attn"]["q_norm"]).reshape(q.shape)
    k = _rms(k.reshape(B, T, -1), lp["attn"]["k_norm"]).reshape(k.shape)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    o = _common.causal_attention(q, k, v)
    return x + jnp.einsum("bthk,hkd->btd", o, lp["attn"]["wo"])


def _layer(x, lp, n_heads: int, theta: float, top_k: int):
    """One layer on x [B, T, D] -> (y, balance, z)."""
    B, T, D = x.shape
    h = _attention(x, lp, n_heads, theta)
    u = _rms(h, lp["ln2"]["w"]).reshape(B * T, D)
    logits = u @ lp["router"]["w"]                            # [N, E]
    E = logits.shape[-1]
    p = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(p, top_k)
    chosen = jax.nn.one_hot(top_e, E, dtype=jnp.float32)      # [N, k, E]
    gates = (chosen * top_p[..., None]).sum(1)                # [N, E]
    out = jnp.concatenate([
        _experts(u[s:s + TOKEN_BLOCK], gates[s:s + TOKEN_BLOCK], lp["mlp"])
        for s in range(0, B * T, TOKEN_BLOCK)])
    f = chosen.sum((0, 1)) / (B * T * top_k)                  # sums to 1
    balance = E * jnp.sum(f * p.mean(0))
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return h + out.reshape(B, T, D), balance, z


def _run(params, tokens, cfg):
    """(logits, balance, z): the two router terms as means over layers."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        balance = z = 0.0
        for i in range(cfg.n_layers):
            x, b_i, z_i = layer(x, _common.layer_slice(params["layers"], i),
                                cfg.n_heads, float(cfg.rope_theta),
                                cfg.expert_top_k)
            balance, z = balance + b_i / cfg.n_layers, z + z_i / cfg.n_layers
        x = _rms(x, params["final_norm"]["w"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32), balance, z


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    return _run(params, tokens, cfg)[0]


def loss(params, tokens, cfg):
    """The whole training loss on rows ``tokens`` [B, T + 1]."""
    logits, balance, z = _run(params, tokens[:, :-1], cfg)
    return (_common.next_token_loss(logits, tokens)
            + BALANCE_WEIGHT * balance + Z_WEIGHT * z)
