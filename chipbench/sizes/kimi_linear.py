"""The sizes a ``kimi_linear`` config file states (Kimi-Linear-48B-A3B's
``config.json`` keys, ``model_type`` ``kimi_linear``; under ``assumed``
what that file has no key for) against the model its factory runs.
``num_experts`` is how many experts the chip HOLDS (the cut: one
expert-parallel rank's share); how many the router scores is
``assumed.router_width``. ``num_hidden_layers`` counts the leading dense
layer (``first_k_dense_replace``) with the expert layers.
``linear_attn_config`` is kept whole: its two lists number the PUBLISHED
layers from 1, and the layers the factory runs are held to the numbers up
to ``num_hidden_layers``. Keys the program has no setting for are held to
what its code does: no bias, no query latent, no group limit on the
router's choice, no rotation."""

from __future__ import annotations

from chipbench.sizes import _common


def check(data: dict, cfg) -> None:
    assumed, linear = data["assumed"], data["linear_attn_config"]
    n = data["num_hidden_layers"]
    numbered = lambda kind: [i + 1 for i, m in enumerate(cfg.layer_mixers)
                             if m == kind]
    _common.compare([
        ("arch", data["arch"], "kimi_linear"),
        ("model_type", data["model_type"], "kimi_linear"),
        ("hidden_size", data["hidden_size"], cfg.d_model),
        ("num_attention_heads", data["num_attention_heads"], cfg.n_heads),
        ("num_key_value_heads", data["num_key_value_heads"], cfg.kv_heads),
        # read by no layer (assumed.head_dim_is)
        ("head_dim", data["head_dim"],
         data["hidden_size"] // data["num_attention_heads"]),
        ("q_lora_rank", data["q_lora_rank"], None),
        ("kv_lora_rank", data["kv_lora_rank"], cfg.kv_latent),
        ("qk_nope_head_dim", data["qk_nope_head_dim"], cfg.d_head_nope),
        ("qk_rope_head_dim", data["qk_rope_head_dim"], cfg.d_head_rope),
        ("v_head_dim", data["v_head_dim"], cfg.d_head_v),
        ("mla_use_nope", data["mla_use_nope"], not cfg.latent_rope),
        ("linear_attn_config.num_heads", linear["num_heads"], cfg.kda_heads),
        ("linear_attn_config.head_dim", linear["head_dim"],
         cfg.kda_head_dim),
        ("linear_attn_config.short_conv_kernel_size",
         linear["short_conv_kernel_size"], cfg.kda_conv),
        ("linear_attn_config.kda_layers up to num_hidden_layers",
         [i for i in linear["kda_layers"] if i <= n], numbered("kda")),
        ("linear_attn_config.full_attn_layers up to num_hidden_layers",
         [i for i in linear["full_attn_layers"] if i <= n],
         numbered("attn")),
        ("kda_low_rank", assumed["kda_low_rank"], cfg.kda_head_dim),
        ("num_hidden_layers", n, cfg.n_layers),
        ("first_k_dense_replace", data["first_k_dense_replace"],
         cfg.n_dense_layers),
        ("moe_layer_freq", data["moe_layer_freq"], 1),
        ("intermediate_size", data["intermediate_size"], cfg.d_ff_dense),
        ("moe_intermediate_size", data["moe_intermediate_size"], cfg.ffn_dim),
        ("num_shared_experts x moe_intermediate_size",
         data["num_shared_experts"] * data["moe_intermediate_size"],
         cfg.d_ff_shared),
        ("num_experts", data["num_experts"], cfg.experts_here),
        ("router_width", assumed["router_width"], cfg.n_experts),
        ("num_experts_per_token", data["num_experts_per_token"],
         cfg.expert_top_k),
        ("moe_renormalize", data["moe_renormalize"], cfg.expert_norm_topk),
        ("routed_scaling_factor", data["routed_scaling_factor"],
         cfg.expert_gate_scale),
        ("moe_router_activation_func", data["moe_router_activation_func"],
         cfg.router_score),
        ("use_grouped_topk", data["use_grouped_topk"], True),
        ("num_expert_group", data["num_expert_group"], 1),
        ("topk_group", data["topk_group"], 1),
        ("num_nextn_predict_layers", data["num_nextn_predict_layers"], 0),
        ("hidden_act", data["hidden_act"], cfg.expert_activation),
        ("vocab_size", data["vocab_size"], cfg.vocab_size),
        ("model_max_length", data["model_max_length"], cfg.max_seq_len),
        ("max_position_embeddings", assumed["max_position_embeddings"],
         cfg.max_seq_len),
        # read by nothing: no layer rotates (assumed.mla_shared_part)
        ("rope_theta", data["rope_theta"], cfg.rope_theta),
        ("rope_scaling", data["rope_scaling"], None),
        ("rms_norm_eps", data["rms_norm_eps"], cfg.norm_eps),
        ("tie_word_embeddings", data["tie_word_embeddings"], cfg.tied),
        ("router_bias", True, cfg.router_bias),
        ("router_bias_rate", assumed["router_bias_rate"],
         cfg.router_bias_rate),
        ("router_aux_loss_coef", assumed["router_aux_loss_coef"],
         cfg.router_aux_weight),
        ("router_z_loss_coef", assumed["router_z_loss_coef"],
         cfg.router_z_weight),
        ("dropless", assumed["dropless"], cfg.expert_capacity_factor is None),
        ("param_dtype", assumed["param_dtype"], cfg.param_dtype),
        ("compute_dtype", assumed["compute_dtype"], cfg.dtype),
    ])
