"""FLOPs of the ``nemotron_h`` arch from its sizes (see ``_common``), as
ONE expert-parallel rank's share runs it. Every layer is ONE sublayer
(``cfg.layer_mixers``: "ssm" a state-space mixer, "attn" attention, "ffn"
experts), so a token passes through a mixer OR through the router, the
shared expert and those of its ``expert_top_k`` experts that the chip
holds, counted AT BALANCE (``top_k x held / all`` of them a token: what
``flops/kanana2.py`` says of this holds here).

A state-space layer's matrices are its one input projection (the gate
``z``, ``[x | B | C]``, the step) and its output projection; the
convolution (8 FLOP a channel) is not counted. **The scan is counted from
the RECURRENCE**, as ``flops/kimi_linear.py`` counts the delta rule: a
token and head makes two products with the ``channels x state`` state, its
update ``Delta x (x) B`` and its read ``S C``, ``2 x 2 x 64 x 128`` forward
and twice that backward; the decay's multiplies and everything a chunked
form adds (``C B^T``, the [chunk, chunk] decay matrices) are not counted,
whatever implements it.

An expert has NO gate projection: two matrices, so SIX grouped matmuls'
worth forward + backward where a SwiGLU expert has nine. The attention
layer: q on 32 heads, k and v on 2, the output projection; the kernels'
seven matmuls a visible (query, key) pair over every QUERY head (k and v
are handed over repeated to the 32), 128 wide. Norms, activations and
gates are no matrix multiplications and count nothing, as everywhere."""

from __future__ import annotations

from chipbench.flops import _common, kanana2

visible_pairs = kanana2.visible_pairs


def _count(cfg, kind: str) -> int:
    return sum(m == kind for m in cfg.layer_mixers)


def _inner(cfg) -> int:
    return cfg.kda_heads * cfg.kda_head_dim


def _ssm_params(cfg) -> float:
    """Parameters in a state-space mixer's matrix multiplications: the
    input projection [z | x B C | dt] and the output projection."""
    wide = 2 * _inner(cfg) + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.kda_heads
    return cfg.d_model * wide + _inner(cfg) * cfg.d_model


def _ssm_leaves(cfg) -> float:
    """What the mixer holds beside its matrices: the convolution with its
    bias, ``dt_bias``, ``A_log``, ``D``, the group norm's weight."""
    conv = _inner(cfg) + 2 * cfg.ssm_groups * cfg.ssm_state
    return ((cfg.kda_conv + cfg.ssm_conv_bias) * conv + 3 * cfg.kda_heads
            + _inner(cfg))


def _attention_params(cfg) -> float:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return 2 * d * h * dh + 2 * d * kv * dh


def held_share(cfg) -> float:
    return cfg.experts_here / cfg.n_experts


def expert_matmul_params(cfg) -> float:
    """Parameters of the held ROUTED experts one token passes through,
    all expert layers, at balance: two matrices an expert."""
    return (_count(cfg, "ffn") * cfg.expert_top_k * held_share(cfg)
            * 2 * cfg.d_model * cfg.ffn_dim)


def shared_matmul_params(cfg) -> float:
    """The shared expert (up and down), all expert layers."""
    return _count(cfg, "ffn") * 2 * cfg.d_model * cfg.d_ff_shared


def matmul_params(cfg) -> float:
    """Parameters in a matrix multiplication ONE token passes through on
    this chip."""
    return (_count(cfg, "ssm") * _ssm_params(cfg)
            + _count(cfg, "attn") * _attention_params(cfg)
            + _count(cfg, "ffn") * cfg.d_model * cfg.n_experts
            + expert_matmul_params(cfg) + shared_matmul_params(cfg)
            + cfg.d_model * cfg.vocab_size)


def n_params(cfg) -> float:
    """Every parameter the chip holds: each layer's ONE norm, the mixers
    with their small leaves, the whole router with its bias, the held
    experts, the shared expert, embedding, untied head, final norm."""
    d = cfg.d_model
    experts = (d * cfg.n_experts + cfg.n_experts
               + cfg.experts_here * 2 * d * cfg.ffn_dim)
    return (_count(cfg, "ssm") * (_ssm_params(cfg) + _ssm_leaves(cfg))
            + _count(cfg, "attn") * _attention_params(cfg)
            + _count(cfg, "ffn") * experts + shared_matmul_params(cfg)
            + cfg.n_layers * d + 2 * d * cfg.vocab_size + d)


def kda_core_flops_per_token(cfg) -> float:
    """The recurrence's FORWARD FLOPs a token, all state-space layers and
    heads: the state's update and its read."""
    return (_count(cfg, "ssm") * cfg.kda_heads
            * 2 * 2 * cfg.kda_head_dim * cfg.ssm_state)


def attention_flops_per_token(cfg, seq_len: int) -> float:
    """Forward FLOPs a token of what mixes tokens: the attention layers'
    QK^T + PV over the visible pairs, and the state-space recurrence."""
    scores = (_count(cfg, "attn") * 2 * 2 * cfg.n_heads * cfg.head_dim
              * visible_pairs(seq_len) / seq_len)
    return scores + kda_core_flops_per_token(cfg)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), attention_flops_per_token(cfg, seq_len))


def experts_train_flops_per_token(cfg) -> float:
    """The held routed experts' own share of ``train_flops_per_token``:
    the two grouped matmuls, forward + backward (SIX matmuls' worth),
    recomputation not counted, at the BALANCED held share."""
    return _common.train_flops_per_token(expert_matmul_params(cfg), 0.0)


def kda_core_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """Model FLOPs of the state-space recurrence in one train step of
    ``rows`` rows: forward + twice that backward, recompute not counted."""
    return 3 * kda_core_flops_per_token(cfg) * seq_len * rows


# -- the attention kernel: the attention layers' alone --------------------------

def attention_kernel_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """Forward QK^T and PV over the visible pairs (2 matmuls) and in the
    backward the scores again, dP, dV, dQ and dK (5), 2 FLOPs a
    multiply-add, ``head_dim`` wide, every query head, the ATTENTION
    layers: no other layer runs these kernels. The recomputed forward is
    not counted, nor what a tile computes of pairs its mask hides."""
    return (_count(cfg, "attn") * 7 * 2 * cfg.n_heads * cfg.head_dim
            * visible_pairs(seq_len) * rows)


def attention_kernel_bytes_per_step(cfg, seq_len: int, rows: int) -> float:
    """The least bytes those kernels move in one train step, as
    ``flops/smallthinker.py`` counts them (k and v expanded to the query
    heads as the program hands them over), over the attention layers."""
    row = cfg.n_heads * cfg.head_dim * 2            # bf16, one tensor a token
    stats = cfg.n_heads * 4
    per_token = (4 * row + stats) + (4 * row + 2 * stats + 3 * row)
    return _count(cfg, "attn") * per_token * seq_len * rows
