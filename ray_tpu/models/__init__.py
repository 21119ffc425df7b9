"""Model library (flagship: decoder-only transformer LMs).

The reference orchestrates external torch models (TorchTrainer user
modules; vLLM engines for ray.llm) and ships none of its own; the
TPU-native framework owns this layer so Train/Serve/bench recipes are
self-contained. See ray_tpu.models.transformer.
"""

from ray_tpu._private import compile_cache
from ray_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy_loss,
    decode_step,
    forward,
    generate,
    gpt2_medium,
    gpt2_small,
    gpt2_xl,
    init_kv_cache,
    init_params,
    init_train_state,
    kanana_2_30b_a3b,
    kimi_linear_48b_a3b,
    llama2_7b,
    llama3_8b,
    lm_loss,
    make_train_step,
    mistral_7b,
    mixtral_8x7b,
    moe_small,
    nemotron_3_nano_30b_a3b,
    olmoe_1b_7b,
    partition_specs,
    phi4_mini_flash_reasoning,
    qwen2_7b,
    qwen3_next_80b_a3b,
    smallthinker_21b_a3b,
    tiny,
    tiny_moe,
    trinity_mini_26b_a3b,
)

# Whoever imports the models compiles them: count it (llm/engine.py and
# the trainers come through here).
compile_cache.install_listener()

__all__ = [
    "TransformerConfig",
    "moe_small",
    "tiny_moe",
    "cross_entropy_loss",
    "decode_step",
    "forward",
    "generate",
    "gpt2_small",
    "gpt2_medium",
    "gpt2_xl",
    "init_kv_cache",
    "init_params",
    "init_train_state",
    "kanana_2_30b_a3b",
    "kimi_linear_48b_a3b",
    "nemotron_3_nano_30b_a3b",
    "trinity_mini_26b_a3b",
    "qwen3_next_80b_a3b",
    "phi4_mini_flash_reasoning",
    "llama2_7b",
    "llama3_8b",
    "lm_loss",
    "make_train_step",
    "mistral_7b",
    "mixtral_8x7b",
    "olmoe_1b_7b",
    "smallthinker_21b_a3b",
    "partition_specs",
    "qwen2_7b",
    "tiny",
]
