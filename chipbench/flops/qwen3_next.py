"""FLOPs of the ``qwen3_next`` arch from its sizes (see ``_common``), as
ONE expert-parallel rank's share runs it: a token passes through its
layer's token mixer (``cfg.layer_mixers``), and in EVERY layer through the
512-wide router, the shared expert with its one-number gate, and those of
its ``expert_top_k`` experts that the chip holds, counted AT BALANCE
(``top_k x held / all`` of them a token: what ``flops/kanana2.py`` says
of this holds here).

A Gated DeltaNet layer's matrices are its one input projection (q and k
by key head, v and the output gate's z by value head), the 2 x heads wide
projection of ``beta`` and the decay, and the output projection; the
short convolutions (8 FLOP a channel) are not counted. **The delta rule
is counted from the RECURRENCE**, as ``flops/kimi_linear.py`` counts it:
a token and VALUE head makes three products with the ``dk x dv`` state
(``S^T k``, the rank-one update, ``S^T q``), ``6 x dk x dv`` forward and
twice that backward; the decay's multiplies and everything a chunked form
adds are not counted.

An attention layer: q and its gate (one projection, 2 x heads x 256), k
and v on the 2 key heads, the output projection; the kernels' seven
matmuls a visible (query, key) pair over every QUERY head (k and v are
handed over repeated to the 16), 256 wide: some 1,900 FLOP a byte at
16,384 tokens against the chip's 240. Norms, the gates' sigmoid / SiLU
and products, and RoPE are no matrix multiplications and count nothing,
as everywhere."""

from __future__ import annotations

from chipbench.flops import _common, kanana2

visible_pairs = kanana2.visible_pairs


def _count(cfg, mixer: str) -> int:
    return sum(m == mixer for m in cfg.layer_mixers)


def _key_heads(cfg) -> int:
    return cfg.linear_key_heads or cfg.kda_heads


def _gdn_params(cfg) -> float:
    """Parameters in a Gated DeltaNet mixer's matrix multiplications."""
    d, width = cfg.d_model, cfg.kda_head_dim
    return (d * 2 * _key_heads(cfg) * width        # q, k
            + d * 2 * cfg.kda_heads * width        # v, z
            + d * 2 * cfg.kda_heads                # beta, the decay
            + cfg.kda_heads * width * d)           # out


def _gdn_leaves(cfg) -> float:
    """What the mixer holds beside its matrices: three convolutions,
    ``dt_bias``, ``A_log``, the head norm's weight."""
    width = cfg.kda_head_dim
    return (cfg.kda_conv * (2 * _key_heads(cfg) + cfg.kda_heads) * width
            + 2 * cfg.kda_heads + width)


def _attention_params(cfg) -> float:
    """q and its gate, k, v and the output projection of one layer."""
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return 3 * d * h * dh + 2 * d * kv * dh


def held_share(cfg) -> float:
    return cfg.experts_here / cfg.n_experts


def expert_matmul_params(cfg) -> float:
    """Parameters of the held ROUTED experts one token passes through,
    all layers, at balance."""
    return (cfg.n_layers * cfg.expert_top_k * held_share(cfg)
            * 3 * cfg.d_model * cfg.ffn_dim)


def shared_matmul_params(cfg) -> float:
    """The shared expert and its gate, all layers."""
    return cfg.n_layers * cfg.d_model * (3 * cfg.d_ff_shared + 1)


def matmul_params(cfg) -> float:
    """Parameters in a matrix multiplication ONE token passes through on
    this chip."""
    return (_count(cfg, "gdn") * _gdn_params(cfg)
            + _count(cfg, "attn") * _attention_params(cfg)
            + cfg.n_layers * cfg.d_model * cfg.n_experts
            + expert_matmul_params(cfg) + shared_matmul_params(cfg)
            + cfg.d_model * cfg.vocab_size)


def n_params(cfg) -> float:
    """Every parameter the chip holds: the mixers with their small leaves
    (attention's two head norms), the two block norms, the whole router,
    the held experts, the shared expert and its gate, embedding, untied
    head, final norm."""
    d = cfg.d_model
    mixers = (_count(cfg, "gdn") * (_gdn_params(cfg) + _gdn_leaves(cfg))
              + _count(cfg, "attn") * (_attention_params(cfg)
                                       + 2 * cfg.head_dim))
    per_layer = (2 * d + d * cfg.n_experts
                 + cfg.experts_here * 3 * d * cfg.ffn_dim)
    return (mixers + cfg.n_layers * per_layer + shared_matmul_params(cfg)
            + 2 * d * cfg.vocab_size + d)


def kda_core_flops_per_token(cfg) -> float:
    """The recurrence's FORWARD FLOPs a token, all Gated DeltaNet layers
    and VALUE heads."""
    return (_count(cfg, "gdn") * cfg.kda_heads
            * 6 * cfg.kda_head_dim * cfg.kda_head_dim)


def attention_flops_per_token(cfg, seq_len: int) -> float:
    """Forward FLOPs a token of what mixes tokens: the attention layers'
    QK^T + PV over the visible pairs, and the DeltaNet layers'
    recurrence."""
    scores = (_count(cfg, "attn") * 2 * 2 * cfg.n_heads * cfg.head_dim
              * visible_pairs(seq_len) / seq_len)
    return scores + kda_core_flops_per_token(cfg)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), attention_flops_per_token(cfg, seq_len))


def experts_train_flops_per_token(cfg) -> float:
    """The held routed experts' own share of ``train_flops_per_token``:
    the three grouped matmuls, forward + backward, recomputation not
    counted, at the BALANCED held share."""
    return _common.train_flops_per_token(expert_matmul_params(cfg), 0.0)


def kda_core_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """Model FLOPs of the delta rule in one train step of ``rows`` rows:
    forward + twice that backward, recompute not counted."""
    return 3 * kda_core_flops_per_token(cfg) * seq_len * rows


# -- the attention kernel: the attention layers' alone --------------------------

def attention_kernel_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """Forward QK^T and PV over the visible pairs (2 matmuls) and in the
    backward the scores again, dP, dV, dQ and dK (5), 2 FLOPs a
    multiply-add, ``head_dim`` wide, every query head, the ATTENTION
    layers: a DeltaNet layer runs none of these kernels. The recomputed
    forward is not counted, nor what a tile computes of pairs its mask
    hides."""
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
