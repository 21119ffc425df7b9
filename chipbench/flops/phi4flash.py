"""FLOPs of the ``phi4flash`` arch from its sizes (see ``_common``). By
``cfg.layer_mixers`` a layer's mixer is "ssm1" (Mamba-1), "attn"
(differential attention, windowed or full by ``cfg.layer_kind``), "gmu" (a
gated memory unit) or "cross" (differential cross-attention); every layer
has the dense SwiGLU; the head is the embedding transposed, ONE matrix.

Matrices a token passes through: a Mamba-1 layer's ``in_proj`` (``[x |
z]``), ``x_proj`` (``[dt_low | B | C]``), ``dt_proj`` and ``out_proj``; an
attention layer's ``Wqkv`` and ``out_proj``; a memory unit's two; a cross
layer's ``Wq`` and ``out_proj`` alone (it reads layer 17's keys and
values: NO key / value projection); three MLP matrices; the head. The
convolution, the norms, the gates and the ``lambda`` combine are no matrix
multiplications and count nothing, as everywhere.

**A differential layer is TWO kernel calls**, each over the 20 query PAIRS
with 64-wide scores against ONE 128-wide value: a visible (query, key)
pair costs a call's scores, their recomputation, dQ and dK 64 wide and ``p
v``, dP and dV 128 wide. The window layers see a band of
``sliding_window`` keys, the full and the cross layers the triangle.

**The scan is counted from the RECURRENCE as written**, whatever
implements it: a token, channel and state index one ``exp``, the decay's
product and add, the input's product, ``C``'s product and add, SIX
operations forward; the Mamba-1 layers only, the memory units have none.
It is vector work: ``kda_core_peak_share`` holds it against the bf16
MATRIX peak, so a scan bound by the vector unit reads in single digits."""

from __future__ import annotations

from chipbench.flops import _common
from chipbench.flops.smallthinker import visible_pairs

_ATTENTION = ("attn", "cross")
_SCORES, _VALUES = 4, 3     # a call's matmuls head_dim wide, 2 x head_dim wide


def _count(cfg, kind: str) -> int:
    return sum(m == kind for m in cfg.layer_mixers)


def _pairs(cfg, seq_len: int) -> int:
    """Visible (query, key) pairs of one row, summed over the attention
    and cross layers."""
    total = 0
    for i, kind in enumerate(cfg.layer_mixers):
        if kind in _ATTENTION:
            windowed = kind == "attn" and cfg.layer_kind(i)[0]
            total += visible_pairs(
                seq_len, cfg.sliding_window if windowed else None)
    return total


def _mixer_matmul_params(cfg, kind: str) -> int:
    d, inner = cfg.d_model, cfg.ssm_inner
    out = cfg.n_heads * cfg.head_dim * d
    return {
        "ssm1": (2 * d * inner + inner * (cfg.ssm_dt_rank + 2 * cfg.ssm_state)
                 + cfg.ssm_dt_rank * inner + inner * d),
        "attn": d * (cfg.n_heads + 2 * cfg.kv_heads) * cfg.head_dim + out,
        "gmu": 2 * d * inner,
        "cross": d * cfg.n_heads * cfg.head_dim + out,
    }[kind]


def _mixer_leaves(cfg, kind: str) -> int:
    """What a mixer holds beside its matrices."""
    d, inner, dh = cfg.d_model, cfg.ssm_inner, cfg.head_dim
    differential = 4 * dh + 2 * dh      # the lambda vectors, the pair norm
    return {
        "ssm1": (inner + inner * cfg.ssm_state + inner
                 + (cfg.kda_conv + cfg.ssm_conv_bias) * inner),
        "attn": ((cfg.n_heads + 2 * cfg.kv_heads) * dh + d + differential),
        "gmu": 0,
        "cross": cfg.n_heads * dh + d + differential,
    }[kind]


def matmul_params(cfg) -> float:
    """Parameters in a matrix multiplication ONE token passes through: the
    tied head once (the embedding's lookup is no multiplication)."""
    return (sum(_mixer_matmul_params(cfg, kind) for kind in cfg.layer_mixers)
            + cfg.n_layers * 3 * cfg.d_model * cfg.ffn_dim
            + cfg.d_model * cfg.vocab_size)


def n_params(cfg) -> float:
    """Every parameter the chip holds: the tied matrix ONCE, two LayerNorms
    a layer and the final one with weight and bias."""
    return (matmul_params(cfg)
            + sum(_mixer_leaves(cfg, kind) for kind in cfg.layer_mixers)
            + (2 * cfg.n_layers + 1) * 2 * cfg.d_model)


def kda_core_flops_per_token(cfg) -> float:
    """The recurrence's FORWARD operations a token, all Mamba-1 layers."""
    return _count(cfg, "ssm1") * 6 * cfg.ssm_inner * cfg.ssm_state


def kda_core_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """The recurrence in one train step of ``rows`` rows: forward + twice
    that backward, recompute not counted."""
    return 3 * kda_core_flops_per_token(cfg) * seq_len * rows


def attention_flops_per_token(cfg, seq_len: int) -> float:
    """Forward FLOPs a token of what mixes tokens: both calls' QK^T (64
    wide) and PV (128 wide) over the visible pairs of every query pair,
    and the recurrence."""
    width = cfg.head_dim + 2 * cfg.head_dim
    scores = 2 * 2 * (cfg.n_heads // 2) * width * _pairs(cfg, seq_len)
    return scores / seq_len + kda_core_flops_per_token(cfg)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), attention_flops_per_token(cfg, seq_len))


# -- the attention kernels: two calls a differential layer --------------------------

def attention_kernel_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """Model FLOPs of the Pallas attention kernels in one train step: per
    call and visible pair the scores, their recomputation in the backward,
    dQ and dK ``head_dim`` wide and ``p v``, dP and dV ``2 x head_dim``
    wide, 2 FLOPs a multiply-add, over ``n_heads / 2`` query pairs, two
    calls a layer. The recomputed forward (remat) is not counted, nor what
    a tile computes of pairs its mask hides."""
    width = _SCORES * cfg.head_dim + _VALUES * 2 * cfg.head_dim
    return (2 * 2 * (cfg.n_heads // 2) * width * _pairs(cfg, seq_len) * rows)


def attention_kernel_bytes_per_step(cfg, seq_len: int, rows: int) -> float:
    """The least bytes those kernels move in one train step, as
    ``flops/smallthinker.py`` counts them, a call: q and k ``head_dim``
    wide, v, o and their cotangents ``2 x head_dim`` wide, k and v expanded
    to the query pairs as the program hands them over."""
    pairs = cfg.n_heads // 2
    narrow, wide = pairs * cfg.head_dim * 2, pairs * 2 * cfg.head_dim * 2
    stats = pairs * 4
    forward = 2 * narrow + 2 * wide + stats
    backward = (2 * narrow + wide) + 2 * wide + 2 * stats + 2 * narrow + wide
    layers = sum(kind in _ATTENTION for kind in cfg.layer_mixers)
    return 2 * layers * (forward + backward) * seq_len * rows
