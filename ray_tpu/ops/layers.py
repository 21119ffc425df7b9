"""Elementwise/normalization building blocks.

Kept as small pure functions so XLA fuses them into the surrounding
matmuls (the HBM-bandwidth rule: never round-trip an activation for a
norm). float32 statistics under bf16 activations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, *, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * weight).astype(x.dtype)


def layer_norm(x, weight, bias, *, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * weight + bias).astype(x.dtype)


def rope_frequencies(head_dim: int, max_len: int, *, theta: float = 10000.0):
    """Precompute RoPE cos/sin tables [max_len, head_dim/2] (float32)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin, *, position_offset: int = 0, positions=None):
    """Rotate [B, T, H, D] by position. ``positions`` overrides the
    arange (needed by sequence-parallel shards and decode steps). Tables
    narrower than D / 2 rotate the FIRST ``2 x their width`` values of a
    head (the two halves of those, paired as ever) and leave the rest of
    the head as it is (a partial rotary factor)."""
    rotated = 2 * cos.shape[-1]
    if rotated < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rotated], cos, sin,
                        position_offset=position_offset, positions=positions),
             x[..., rotated:]], axis=-1)
    t = x.shape[1]
    if positions is None:
        positions = position_offset + jnp.arange(t)
    c = cos[positions][None, :, None, :]
    s = sin[positions][None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: (silu(x·Wg) ⊙ (x·Wu)) · Wd — three MXU matmuls with
    the elementwise glue fused between them."""
    g = jax.nn.silu(x @ w_gate)
    u = x @ w_up
    return (g * u) @ w_down


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    """GPT-2 style MLP."""
    h = jax.nn.gelu(x @ w_in + b_in, approximate=True)
    return h @ w_out + b_out
