"""Plain reference of the ``gpt2`` arch: learned positions, LayerNorm
(eps 1e-5), multi-head attention, GELU (tanh form, GPT-2's gelu_new)
MLP with biases, head tied to the embedding. float32 throughout under
``default_matmul_precision("highest")``, one layer at a time. As in the
program, the attention projections carry no bias (a departure from
GPT-2 that the configuration file lists).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common


def _ln(x, w, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _layer(x, lp):
    h = _ln(x, lp["ln1"]["w"], lp["ln1"]["b"])
    q = jnp.einsum("btd,dhk->bthk", h, lp["attn"]["wq"])
    k = jnp.einsum("btd,dhk->bthk", h, lp["attn"]["wk"])
    v = jnp.einsum("btd,dhk->bthk", h, lp["attn"]["wv"])
    o = _common.causal_attention(q, k, v)
    x = x + jnp.einsum("bthk,hkd->btd", o, lp["attn"]["wo"])
    h = _ln(x, lp["ln2"]["w"], lp["ln2"]["b"])
    m = jax.nn.gelu(h @ lp["mlp"]["w_in"] + lp["mlp"]["b_in"],
                    approximate=True) @ lp["mlp"]["w_out"] + lp["mlp"]["b_out"]
    return x + m


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    layer = jax.jit(_layer)
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[1]
        x = (params["embed"]["tokens"][tokens].astype(jnp.float32)
             + params["embed"]["pos"][:T].astype(jnp.float32))
        for i in range(cfg.n_layers):
            x = layer(x, _common.layer_slice(params["layers"], i))
        x = _ln(x, params["final_norm"]["w"].astype(jnp.float32),
                params["final_norm"]["b"].astype(jnp.float32))
        return x @ params["embed"]["tokens"].astype(jnp.float32).T
