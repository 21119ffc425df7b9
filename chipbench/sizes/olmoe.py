"""The sizes an ``olmoe`` config file states (OLMoE's ``config.json``
keys, and under ``assumed`` the two loss weights of its recipe) against
the model its factory runs. Keys the program has no setting for are held
to what its code does: no bias, no clipping, SiLU, plain RoPE, the eps of
``ops.layers.rms_norm``."""

from __future__ import annotations

import inspect

from chipbench.sizes import _common


def check(data: dict, cfg) -> None:
    from ray_tpu.ops import layers

    eps = inspect.signature(layers.rms_norm).parameters["eps"].default
    assumed = data["assumed"]
    _common.compare([
        ("model_type", data["model_type"], data["arch"]),
        ("hidden_size", data["hidden_size"], cfg.d_model),
        ("intermediate_size", data["intermediate_size"], cfg.ffn_dim),
        ("num_attention_heads", data["num_attention_heads"], cfg.n_heads),
        ("num_key_value_heads", data["num_key_value_heads"], cfg.kv_heads),
        ("num_hidden_layers", data["num_hidden_layers"], cfg.n_layers),
        ("vocab_size", data["vocab_size"], cfg.vocab_size),
        ("max_position_embeddings", data["max_position_embeddings"],
         cfg.max_seq_len),
        ("rope_theta", data["rope_theta"], cfg.rope_theta),
        ("rope_scaling", data["rope_scaling"], None),
        ("rms_norm_eps", data["rms_norm_eps"], eps),
        ("hidden_act", data["hidden_act"], "silu"),
        ("attention_bias", data["attention_bias"], False),
        ("clip_qkv", data["clip_qkv"], None),
        ("tie_word_embeddings", data["tie_word_embeddings"], cfg.tied),
        ("num_experts", data["num_experts"], cfg.n_experts),
        ("num_experts_per_tok", data["num_experts_per_tok"],
         cfg.expert_top_k),
        ("norm_topk_prob", data["norm_topk_prob"], cfg.expert_norm_topk),
        ("head_dim", 128, cfg.head_dim),
        ("qk_norm", assumed["qk_norm"], cfg.qk_norm),
        ("dropless", assumed["dropless"], cfg.expert_capacity_factor is None),
        ("router_aux_loss_coef", assumed["router_aux_loss_coef"],
         cfg.router_aux_weight),
        ("router_z_loss_coef", assumed["router_z_loss_coef"],
         cfg.router_z_weight),
        ("param_dtype", assumed["param_dtype"], cfg.param_dtype),
        ("compute_dtype", assumed["compute_dtype"], cfg.dtype),
    ])
