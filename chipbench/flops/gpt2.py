"""FLOPs of the ``gpt2`` arch from its sizes (see ``_common``)."""

from __future__ import annotations

from chipbench.flops import _common


def matmul_params(cfg) -> float:
    """q, k, v, o, the two MLP matrices, and the tied head (the same
    matrix as the embedding, used once as a matmul)."""
    d, f = cfg.d_model, cfg.ffn_dim
    return cfg.n_layers * (4 * d * d + 2 * d * f) + d * cfg.vocab_size


def n_params(cfg) -> float:
    """Every parameter: matrices, MLP biases, LayerNorms, positions."""
    d, f = cfg.d_model, cfg.ffn_dim
    return (matmul_params(cfg) + cfg.max_seq_len * d
            + cfg.n_layers * (f + d + 4 * d) + 2 * d)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), _common.attention_flops_per_token(
            cfg.n_layers, cfg.n_heads, cfg.head_dim, seq_len))
