"""FLOPs of the ``kimi_linear`` arch from its sizes (see ``_common``), as
ONE expert-parallel rank's share runs it: a token passes through its
layer's token mixer (``cfg.layer_mixers``), through the dense FFN of the
leading layer, and in every expert layer through the 256-wide router, the
shared expert, and those of its ``expert_top_k`` experts that the chip
holds, counted AT BALANCE (``top_k x held / all`` of them a token: what
``flops/kanana2.py`` says of this holds here).

A latent-attention layer is kanana-2's (``flops/kanana2.py``): query,
latent-down, latent-up and output projections, scores over 192 columns
and values over 128 on the visible pairs of a full causal layer.

A KDA layer: the q, k, v and output projections, the two low-rank maps
(decay, output gate) and ``beta``'s projection are its matrices; the
short convolutions (8 FLOP a channel) are not counted. **The delta rule
is counted from the RECURRENCE**, not from what implements it: a token
and head makes three products with the ``dk x dv`` state, ``S^T k`` (what
the state holds under this key), the rank-one update ``k u^T`` and
``S^T q``, 2 FLOPs a multiply-add: ``6 x dk x dv`` forward and twice
that backward. The decay's ``dk x dv`` multiplies, and everything a
chunked form adds (the intra-chunk ``A`` and ``B``, the triangular
inverse, a recomputed forward), are not counted: the count does not
change with the chunk or with a kernel."""

from __future__ import annotations

from chipbench.flops import _common, kanana2

visible_pairs = kanana2.visible_pairs


def _count(cfg, mixer: str) -> int:
    return sum(m == mixer for m in cfg.layer_mixers)


def _kda_params(cfg) -> float:
    """Parameters in a KDA mixer's matrix multiplications."""
    d, inner = cfg.d_model, cfg.kda_heads * cfg.kda_head_dim
    rank = cfg.kda_head_dim
    return (4 * d * inner                       # q, k, v, out
            + 2 * (d * rank + rank * inner)     # decay, output gate
            + d * cfg.kda_heads)                # beta


def _kda_leaves(cfg) -> float:
    """What a KDA mixer holds beside its matrices: three convolutions,
    ``dt_bias``, ``A_log``, the head norm's weight."""
    inner = cfg.kda_heads * cfg.kda_head_dim
    return 3 * cfg.kda_conv * inner + inner + cfg.kda_heads + cfg.kda_head_dim


def _expert_layers(cfg) -> int:
    return cfg.n_layers - cfg.n_dense_layers


def held_share(cfg) -> float:
    return cfg.experts_here / cfg.n_experts


def expert_matmul_params(cfg) -> float:
    return (_expert_layers(cfg) * cfg.expert_top_k * held_share(cfg)
            * 3 * cfg.d_model * cfg.ffn_dim)


def shared_matmul_params(cfg) -> float:
    return 3 * cfg.d_model * (_expert_layers(cfg) * cfg.d_ff_shared
                              + cfg.n_dense_layers * cfg.d_ff_dense)


def matmul_params(cfg) -> float:
    """Parameters in a matrix multiplication ONE token passes through on
    this chip."""
    return (_count(cfg, "attn") * kanana2._attention_params(cfg)
            + _count(cfg, "kda") * _kda_params(cfg)
            + _expert_layers(cfg) * cfg.d_model * cfg.n_experts
            + expert_matmul_params(cfg) + shared_matmul_params(cfg)
            + cfg.d_model * cfg.vocab_size)


def n_params(cfg) -> float:
    """Every parameter the chip holds."""
    d = cfg.d_model
    mixers = (_count(cfg, "attn") * (kanana2._attention_params(cfg)
                                     + cfg.kv_latent)
              + _count(cfg, "kda") * (_kda_params(cfg) + _kda_leaves(cfg)))
    per_expert_layer = (d * cfg.n_experts + cfg.n_experts
                        + cfg.experts_here * 3 * d * cfg.ffn_dim)
    return (mixers + cfg.n_layers * 2 * d
            + _expert_layers(cfg) * per_expert_layer
            + shared_matmul_params(cfg) + 2 * d * cfg.vocab_size + d)


def kda_core_flops_per_token(cfg) -> float:
    """The recurrence's FORWARD FLOPs a token, all KDA layers and heads."""
    return (_count(cfg, "kda") * cfg.kda_heads
            * 6 * cfg.kda_head_dim * cfg.kda_head_dim)


def attention_flops_per_token(cfg, seq_len: int) -> float:
    """Forward FLOPs a token of what mixes tokens: the latent layers'
    QK^T (192 wide) + PV (128 wide) over the visible pairs, and the KDA
    layers' recurrence."""
    width = cfg.head_dim + cfg.d_head_v
    latent = (_count(cfg, "attn") * 2 * cfg.n_heads * width
              * visible_pairs(seq_len) / seq_len)
    return latent + kda_core_flops_per_token(cfg)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), attention_flops_per_token(cfg, seq_len))


def kda_core_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """Model FLOPs of the delta rule in one train step of ``rows`` rows:
    forward + twice that backward, recompute not counted."""
    return 3 * kda_core_flops_per_token(cfg) * seq_len * rows


# -- the attention kernel: the latent layers' alone ---------------------------

def attention_kernel_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """``flops/kanana2.py``'s count a layer (forward QK^T over 192 and PV
    over 128; backward the scores again, dQ and dK over 192, dP and dV
    over 128) over the LATENT layers: a KDA layer runs no kernel."""
    qk, v = cfg.head_dim, cfg.d_head_v
    per_pair = 2 * (qk + v) + 2 * (3 * qk + 2 * v)
    return (_count(cfg, "attn") * cfg.n_heads * per_pair
            * visible_pairs(seq_len) * rows)


def attention_kernel_bytes_per_step(cfg, seq_len: int, rows: int) -> float:
    """``flops/kanana2.py``'s bytes a layer over the latent layers: some
    5,000 FLOP a byte at 16,384 tokens against the chip's 240."""
    return (kanana2.attention_kernel_bytes_per_step(cfg, seq_len, rows)
            * _count(cfg, "attn") / cfg.n_layers)
