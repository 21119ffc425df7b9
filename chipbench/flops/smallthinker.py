"""FLOPs of the ``smallthinker`` arch from its sizes (see ``_common``),
as ONE expert-parallel rank's share runs it: a token passes through q,
k, v, o and the 64-wide router in every layer, through the head's slice,
and through those of its ``expert_top_k`` experts that the chip holds.
The experts are counted AT BALANCE, ``top_k x held / all`` of them a
token: a count from the sizes, the only thing these functions are given.
It is NOT what a seeded run sends: the cell's router is skewed (PERF.md
section 6, PR 31: the step's own counter ``moe_held_share`` read 0.10 to
0.44 from step to step on the chip, 0.236-0.238 in the mean over a
window against 0.25), so ``moe_experts_peak_share`` and
``experts_train_flops_per_token`` stand 5-6% over the FLOPs the held
experts ran and ``mfu`` about 0.5% (the experts are a tenth of the
step's FLOPs). The harness cannot hand the counter over: its train
driver fetches the loss alone. Attention counts the visible (query, key)
pairs exactly, for both kinds of layer: a global layer's query i sees
i + 1 keys, a windowed layer's ``min(i + 1, window)``."""

from __future__ import annotations

from chipbench.flops import _common


def _attention_params(cfg) -> float:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return d * h * dh + 2 * d * kv * dh + h * dh * d


def held_share(cfg) -> float:
    """The share of a token's assignments that meets a held expert at
    balance."""
    return cfg.experts_here / cfg.n_experts


def expert_matmul_params(cfg) -> float:
    """Parameters of the held experts one token passes through, all
    layers, at balance: ``expert_top_k x held_share`` gated FFNs."""
    return (cfg.n_layers * cfg.expert_top_k * held_share(cfg)
            * 3 * cfg.d_model * cfg.ffn_dim)


def matmul_params(cfg) -> float:
    """Parameters in a matrix multiplication ONE token passes through
    on this chip."""
    per_layer = _attention_params(cfg) + cfg.d_model * cfg.n_experts
    return (cfg.n_layers * per_layer + expert_matmul_params(cfg)
            + cfg.d_model * cfg.vocab_size)


def n_params(cfg) -> float:
    """Every parameter the chip holds: the held experts, the whole
    router, the block norms, embedding, untied head, final norm."""
    d, f = cfg.d_model, cfg.ffn_dim
    per_layer = (_attention_params(cfg) + d * cfg.n_experts
                 + cfg.experts_here * 3 * d * f + 2 * d)
    return cfg.n_layers * per_layer + 2 * d * cfg.vocab_size + d


def visible_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs of one row a causal layer's attention sees."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layer_windows(cfg) -> list:
    """The window (or None) of each layer the factory runs."""
    kinds = [cfg.layer_kind(i) or (False, True) for i in range(cfg.n_layers)]
    return [cfg.sliding_window if windowed else None for windowed, _ in kinds]


def attention_flops_per_token(cfg, seq_len: int) -> float:
    """Forward QK^T + PV FLOPs a token, all layers, over the pairs each
    layer's mask leaves visible."""
    pairs = sum(visible_pairs(seq_len, w) for w in layer_windows(cfg))
    return 2 * 2 * cfg.n_heads * cfg.head_dim * pairs / seq_len


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), attention_flops_per_token(cfg, seq_len))


def experts_train_flops_per_token(cfg) -> float:
    """The held experts' own share of ``train_flops_per_token``: the
    three grouped matmuls, forward + backward, recomputation not
    counted, at the BALANCED held share (the module's docstring says
    how far a seeded run is from it)."""
    return _common.train_flops_per_token(expert_matmul_params(cfg), 0.0)


# -- the attention kernel -----------------------------------------------------

def attention_kernel_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """Model FLOPs of the Pallas attention kernels in one train step of
    ``rows`` rows: forward QK^T and PV over the visible pairs (2
    matmuls), and in the backward the scores again, dP, dV, dQ and dK (5
    matmuls the algorithm needs whatever the tiling: the probabilities
    are never stored), 2 FLOPs a multiply-add, ``head_dim`` wide. The
    recomputed forward (remat) is not counted, and neither is what a
    tile computes of pairs its mask hides."""
    pairs = sum(visible_pairs(seq_len, w) for w in layer_windows(cfg))
    return (2 + 5) * 2 * cfg.n_heads * cfg.head_dim * pairs * rows


def attention_kernel_bytes_per_step(cfg, seq_len: int, rows: int) -> float:
    """The least bytes those kernels move in one train step: forward
    reads q, k, v and writes o (+ the float32 logsumexp); the backward
    reads q, k, v, o's cotangent, the statistics and writes dq, dk, dv;
    k and v expanded to the query heads as the program hands them over.
    Far under the FLOPs' time at these lengths (about 500 FLOP a byte
    against the chip's 240): compute is the kernel's bound."""
    row = cfg.n_heads * cfg.head_dim * 2            # bf16, one tensor a token
    stats = cfg.n_heads * 4
    per_token = (4 * row + stats) + (4 * row + 2 * stats + 3 * row)
    return cfg.n_layers * per_token * seq_len * rows
