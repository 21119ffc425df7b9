"""FLOPs of the ``llama`` arch from its sizes (see ``_common``)."""

from __future__ import annotations

from chipbench.flops import _common


def matmul_params(cfg) -> float:
    """Parameters that sit in a matrix multiplication a token passes
    through: q, k, v, o, the three SwiGLU matrices, and the head. The
    embedding is a lookup and the norms are elementwise."""
    d, h, kv, dh, f = (cfg.d_model, cfg.n_heads, cfg.kv_heads,
                       cfg.head_dim, cfg.ffn_dim)
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    return cfg.n_layers * per_layer + d * cfg.vocab_size


def n_params(cfg) -> float:
    """Every parameter (against ``cfg.num_params()`` in the tests)."""
    return (matmul_params(cfg) + cfg.vocab_size * cfg.d_model
            + (2 * cfg.n_layers + 1) * cfg.d_model)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), _common.attention_flops_per_token(
            cfg.n_layers, cfg.n_heads, cfg.head_dim, seq_len))
