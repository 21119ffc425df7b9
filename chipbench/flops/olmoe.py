"""FLOPs of the ``olmoe`` arch from its sizes (see ``_common``): a token
passes through the router and ``expert_top_k`` of the experts, whatever
the program spends on sorting and moving it."""

from __future__ import annotations

from chipbench.flops import _common


def _attention_params(cfg) -> float:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return d * h * dh + 2 * d * kv * dh + h * dh * d


def expert_matmul_params(cfg) -> float:
    """Parameters of the ``expert_top_k`` SwiGLUs one token passes
    through, all layers."""
    return cfg.n_layers * cfg.expert_top_k * 3 * cfg.d_model * cfg.ffn_dim


def matmul_params(cfg) -> float:
    """Parameters in a matrix multiplication ONE token passes through:
    q, k, v, o, the router, ``expert_top_k`` SwiGLUs, and the head."""
    per_layer = _attention_params(cfg) + cfg.d_model * cfg.n_experts
    return (cfg.n_layers * per_layer + expert_matmul_params(cfg)
            + cfg.d_model * cfg.vocab_size)


def n_params(cfg) -> float:
    """Every parameter: all the experts, the q / k norm weights, the block
    norms, embedding, untied head, final norm."""
    d, f = cfg.d_model, cfg.ffn_dim
    qk_norm = (cfg.n_heads + cfg.kv_heads) * cfg.head_dim
    per_layer = (_attention_params(cfg) + qk_norm + d * cfg.n_experts
                 + cfg.n_experts * 3 * d * f + 2 * d)
    return cfg.n_layers * per_layer + 2 * d * cfg.vocab_size + d


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), _common.attention_flops_per_token(
            cfg.n_layers, cfg.n_heads, cfg.head_dim, seq_len))


def experts_train_flops_per_token(cfg) -> float:
    """The experts' own share of ``train_flops_per_token``: the three
    grouped matmuls, forward + backward, recomputation not counted."""
    return _common.train_flops_per_token(expert_matmul_params(cfg), 0.0)
