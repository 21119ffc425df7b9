"""Model FLOPs a token, the way MFU counts them: 2 per multiply-add in
every matrix multiplication of the forward pass, 3x that for forward +
backward; recomputation (remat) is not counted. Attention's score and
value products are causal: on average T/2 keys a query."""

from __future__ import annotations


def attention_flops_per_token(n_layers: int, n_heads: int, head_dim: int,
                              seq_len: int) -> float:
    """Forward QK^T + PV FLOPs a token, causal (T/2 keys on average)."""
    return n_layers * 2 * 2 * n_heads * head_dim * (seq_len / 2.0)


def train_flops_per_token(matmul_params: float, attn_fwd: float) -> float:
    return 3.0 * (2.0 * matmul_params + attn_fwd)
