"""Shared plain pieces of the references: float32 attention in query
blocks (so long rows fit), log-softmax loss. No kernels, no cache."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_attention(q, k, v, *, block_q: int = 1024):
    """q, k, v [B, T, H, Dh] float32 (k, v already repeated to H heads)
    -> [B, T, H, Dh]. Softmax over the whole context, one block of query
    positions at a time so the [T, T] scores never exist at once."""
    B, T, H, Dh = q.shape
    scale = 1.0 / (Dh ** 0.5)
    outs = []
    for s in range(0, T, block_q):
        e = min(T, s + block_q)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:e], k[:, :e]) * scale
        mask = (jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :])
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :e]))
    return jnp.concatenate(outs, axis=1)


def layer_slice(layers, i: int):
    """Layer ``i`` of the program's stacked [n_layers, ...] parameters."""
    return jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)


def token_logprobs(logits, targets):
    """log p(targets) under float32 logits [B, T, V]; targets [B, T]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def next_token_loss(logits, tokens):
    """Mean next-token cross entropy of rows ``tokens`` [B, T + 1] given
    the logits of their first T positions."""
    return -token_logprobs(logits, tokens[:, 1:]).mean()
