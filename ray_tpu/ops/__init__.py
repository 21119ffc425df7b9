"""ray_tpu.ops: TPU compute kernels (Pallas) and fusable building blocks.

The compute layer the reference leaves to torch/vLLM; here it is owned:
flash attention (Pallas), ring attention for sequence parallelism
(greenfield vs the reference — SURVEY.md §2.4), and norm/rope/mlp blocks
shaped for XLA fusion.
"""

from ray_tpu.ops.attention import (
    FLASH_LSE_NAME,
    FLASH_OUT_NAME,
    attention,
    blockwise_attention,
    dot_product_attention,
    flash_attention,
)
from ray_tpu.ops.layers import (
    apply_rope,
    gelu_mlp,
    layer_norm,
    rms_norm,
    rope_frequencies,
    swiglu,
)
from ray_tpu.ops.ring_attention import ring_attention, ring_attention_sharded

__all__ = [
    "attention",
    "blockwise_attention",
    "dot_product_attention",
    "flash_attention",
    "FLASH_OUT_NAME",
    "FLASH_LSE_NAME",
    "apply_rope",
    "gelu_mlp",
    "layer_norm",
    "rms_norm",
    "rope_frequencies",
    "swiglu",
    "ring_attention",
    "ring_attention_sharded",
]
