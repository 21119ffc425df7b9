"""FLOPs of the ``afmoe`` arch from its sizes (see ``_common``), as ONE
expert-parallel rank's share runs it: a token passes through q, k, v, the
output GATE's projection and the output projection of every layer,
through the dense FFN of the leading layers, and in every expert layer
through the 128-wide router, the shared expert, and those of its
``expert_top_k`` experts that the chip holds. The routed experts are
counted AT BALANCE, ``top_k x held / all`` of them a token: a count from
the sizes (what ``flops/smallthinker.py`` says of this holds here;
``moe_held_off_balance`` says how far a step was). Attention counts the
visible (query, key) pairs exactly, for both kinds of layer: a full
layer's query i sees i + 1 keys, a sliding layer's ``min(i + 1,
window)``; the kernels' seven matmuls a pair are taken over every query
head (k and v are handed over repeated to the 32), some 1,300 FLOP a byte
in a sliding layer at 16,384 tokens against the chip's 240. Norms, the
gate's sigmoid and product, and RoPE are no matrix multiplications and
count nothing, as everywhere."""

from __future__ import annotations

from chipbench.flops import _common
# the routed experts, the shared expert and the dense layers are counted
# as kanana-2's are, the kernels' pairs and bytes as SmallThinker's
from chipbench.flops.kanana2 import (  # noqa: F401 (read by name)
    expert_matmul_params, experts_train_flops_per_token, held_share,
    shared_matmul_params)
from chipbench.flops.smallthinker import (  # noqa: F401 (read by name)
    attention_flops_per_token, attention_kernel_bytes_per_step,
    attention_kernel_flops_per_step, layer_windows, visible_pairs)


def _attention_params(cfg) -> float:
    """q, k, v, the gate and the output projection of one layer."""
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return 3 * d * h * dh + 2 * d * kv * dh


def _expert_layers(cfg) -> int:
    return cfg.n_layers - cfg.n_dense_layers


def matmul_params(cfg) -> float:
    """Parameters in a matrix multiplication ONE token passes through on
    this chip."""
    return (cfg.n_layers * _attention_params(cfg)
            + _expert_layers(cfg) * cfg.d_model * cfg.n_experts
            + expert_matmul_params(cfg) + shared_matmul_params(cfg)
            + cfg.d_model * cfg.vocab_size)


def n_params(cfg) -> float:
    """Every parameter the chip holds: attention with its gate and its two
    head norms, the four block norms, the dense FFNs, the held experts,
    the shared expert, the whole router and its bias, embedding, untied
    head, final norm."""
    d = cfg.d_model
    per_layer = _attention_params(cfg) + 2 * cfg.head_dim + 4 * d
    per_expert_layer = (d * cfg.n_experts + cfg.n_experts
                        + cfg.experts_here * 3 * d * cfg.ffn_dim)
    return (cfg.n_layers * per_layer
            + _expert_layers(cfg) * per_expert_layer
            + shared_matmul_params(cfg) + 2 * d * cfg.vocab_size + d)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), attention_flops_per_token(cfg, seq_len))
