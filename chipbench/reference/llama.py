"""Plain reference of the ``llama`` arch (Mistral-7B-v0.1's block):
RMSNorm (eps 1e-5), rotary positions (rotate-half, theta from the
config), grouped-query attention, SwiGLU, untied head. float32
throughout under ``default_matmul_precision("highest")``; one layer at a
time over the program's stacked weights, so it runs on what the chip or
the host can hold beside them. Sliding-window attention is not applied
(neither does the program; contexts here are <= the 4096 window).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common


def _rms(x, w, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE on [B, T, H, Dh] at positions 0..T-1."""
    T, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.outer(jnp.arange(T, dtype=jnp.float32), inv)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, n_heads: int, kv_heads: int, theta: float):
    h = _rms(x, lp["ln1"]["w"])
    q = jnp.einsum("btd,dhk->bthk", h, lp["attn"]["wq"])
    k = jnp.einsum("btd,dhk->bthk", h, lp["attn"]["wk"])
    v = jnp.einsum("btd,dhk->bthk", h, lp["attn"]["wv"])
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    o = _common.causal_attention(q, k, v)
    x = x + jnp.einsum("bthk,hkd->btd", o, lp["attn"]["wo"])
    h = _rms(x, lp["ln2"]["w"])
    m = (jax.nn.silu(h @ lp["mlp"]["w_gate"]) * (h @ lp["mlp"]["w_up"])) \
        @ lp["mlp"]["w_down"]
    return x + m


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        for i in range(cfg.n_layers):
            x = layer(x, _common.layer_slice(params["layers"], i),
                      cfg.n_heads, cfg.kv_heads, float(cfg.rope_theta))
        x = _rms(x, params["final_norm"]["w"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32)
