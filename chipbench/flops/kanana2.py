"""FLOPs of the ``kanana2`` arch from its sizes (see ``_common``), as ONE
expert-parallel rank's share runs it: a token passes through the query,
latent-down, latent-up and output projections of every layer (latent
attention: ``flops`` are counted as the layer is written, the latent NOT
absorbed into the query or the output), through the dense FFN of the
leading layers, and in every expert layer through the 128-wide router,
the shared expert, and those of its ``expert_top_k`` experts that the
chip holds. The routed experts are counted AT BALANCE, ``top_k x held /
all`` of them a token: a count from the sizes (what
``flops/smallthinker.py`` says of this holds here: a seeded router is
not balanced, and ``moe_held_off_balance`` says how far a step was).
Attention is full and causal in every layer: a query sees itself and all
before it, scores over 192 columns (128 without positions + 64 rotary),
values over 128."""

from __future__ import annotations

from chipbench.flops import _common


def _attention_params(cfg) -> float:
    d, h = cfg.d_model, cfg.n_heads
    return (d * h * cfg.head_dim                          # query
            + d * (cfg.kv_latent + cfg.d_head_rope)       # latent down
            + cfg.kv_latent * h * (cfg.d_head_nope + cfg.d_head_v)   # up
            + h * cfg.d_head_v * d)                       # output


def _expert_layers(cfg) -> int:
    return cfg.n_layers - cfg.n_dense_layers


def held_share(cfg) -> float:
    """The share of a token's assignments that meets a held expert at
    balance."""
    return cfg.experts_here / cfg.n_experts


def expert_matmul_params(cfg) -> float:
    """Parameters of the held ROUTED experts one token passes through,
    all expert layers, at balance."""
    return (_expert_layers(cfg) * cfg.expert_top_k * held_share(cfg)
            * 3 * cfg.d_model * cfg.ffn_dim)


def shared_matmul_params(cfg) -> float:
    """Parameters of the shared experts and of the leading dense FFNs:
    what every token passes through whole."""
    return 3 * cfg.d_model * (_expert_layers(cfg) * cfg.d_ff_shared
                              + cfg.n_dense_layers * cfg.d_ff_dense)


def matmul_params(cfg) -> float:
    """Parameters in a matrix multiplication ONE token passes through on
    this chip."""
    return (cfg.n_layers * _attention_params(cfg)
            + _expert_layers(cfg) * cfg.d_model * cfg.n_experts
            + expert_matmul_params(cfg) + shared_matmul_params(cfg)
            + cfg.d_model * cfg.vocab_size)


def n_params(cfg) -> float:
    """Every parameter the chip holds: attention with its latent norm,
    the block norms, the dense FFNs, the held experts, the shared expert,
    the whole router and its bias, embedding, untied head, final norm."""
    d = cfg.d_model
    per_layer = _attention_params(cfg) + cfg.kv_latent + 2 * d
    per_expert_layer = (d * cfg.n_experts + cfg.n_experts
                        + cfg.experts_here * 3 * d * cfg.ffn_dim)
    return (cfg.n_layers * per_layer
            + _expert_layers(cfg) * per_expert_layer
            + shared_matmul_params(cfg) + 2 * d * cfg.vocab_size + d)


def visible_pairs(seq_len: int) -> int:
    """(query, key) pairs of one row a causal layer's attention sees."""
    return seq_len * (seq_len + 1) // 2


def attention_flops_per_token(cfg, seq_len: int) -> float:
    """Forward QK^T (192 wide) + PV (128 wide) FLOPs a token, all
    layers, over the visible pairs."""
    width = cfg.head_dim + cfg.d_head_v
    return (cfg.n_layers * 2 * cfg.n_heads * width
            * visible_pairs(seq_len) / seq_len)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return _common.train_flops_per_token(
        matmul_params(cfg), attention_flops_per_token(cfg, seq_len))


def experts_train_flops_per_token(cfg) -> float:
    """The held routed experts' own share of ``train_flops_per_token``:
    the three grouped matmuls, forward + backward, recomputation not
    counted, at the BALANCED held share. The shared expert runs under its
    own scope and is not in it."""
    return _common.train_flops_per_token(expert_matmul_params(cfg), 0.0)


# -- the attention kernel -----------------------------------------------------

def attention_kernel_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """Model FLOPs of the Pallas attention kernels in one train step of
    ``rows`` rows, over the visible pairs, 2 FLOPs a multiply-add, a head:
    forward QK^T over ``head_dim`` (192) columns and PV over ``d_head_v``
    (128); in the backward the scores again, dQ and dK over 192 each, dP
    and dV over 128 each: 2 x (192 + 128) + 2 x (3 x 192 + 2 x 128) a
    pair. The recomputed forward (remat) is not counted, and neither is
    what a tile computes of pairs its mask hides."""
    qk, v = cfg.head_dim, cfg.d_head_v
    per_pair = 2 * (qk + v) + 2 * (3 * qk + 2 * v)
    return (cfg.n_layers * cfg.n_heads * per_pair * visible_pairs(seq_len)
            * rows)


def attention_kernel_bytes_per_step(cfg, seq_len: int, rows: int) -> float:
    """The least bytes those kernels move in one train step, bfloat16:
    forward reads q (192 a head), k_nope and v (128 each a head) and the
    ONE rotary key (64 a token, not a head), writes o (128) and the
    float32 logsumexp; the backward reads all of those, o's cotangent
    and the two statistics, and writes dq, dk_nope, dv and a rotary-key
    gradient a head. Some 2,600 FLOP a byte at 8,192 tokens against the
    chip's 240: compute is the kernels' bound."""
    h, nope, rope, v = (cfg.n_heads, cfg.d_head_nope, cfg.d_head_rope,
                        cfg.d_head_v)
    operands = h * (nope + rope + nope + v) * 2 + rope * 2     # q, k, v, k_r
    out = h * v * 2
    stats = h * 4
    forward = operands + out + stats
    backward = (operands + 2 * out + 2 * stats
                + h * (nope + rope + nope + v + rope) * 2)
    return cfg.n_layers * (forward + backward) * seq_len * rows
