"""The sizes a ``kanana2`` config file states (kanana-2's ``config.json``
keys, ``model_type`` ``deepseek_v3``; under ``assumed`` what that file
has no key for) against the model its factory runs. ``n_routed_experts``
is how many experts the chip HOLDS (the cut: one expert-parallel rank's
share); how many the router scores is ``assumed.router_width``.
``num_hidden_layers`` counts the leading dense layers
(``first_k_dense_replace``) with the expert layers. Keys the program has
no setting for are held to what its code does: no bias on a projection,
no query latent, no group limit on the router's choice, plain RoPE."""

from __future__ import annotations

from chipbench.sizes import _common


def check(data: dict, cfg) -> None:
    assumed = data["assumed"]
    _common.compare([
        ("arch", data["arch"], "kanana2"),
        ("model_type", data["model_type"], "deepseek_v3"),
        ("hidden_size", data["hidden_size"], cfg.d_model),
        ("num_attention_heads", data["num_attention_heads"], cfg.n_heads),
        ("num_key_value_heads", data["num_key_value_heads"], cfg.kv_heads),
        ("q_lora_rank", data["q_lora_rank"], None),
        ("kv_lora_rank", data["kv_lora_rank"], cfg.kv_latent),
        ("qk_nope_head_dim", data["qk_nope_head_dim"], cfg.d_head_nope),
        ("qk_rope_head_dim", data["qk_rope_head_dim"], cfg.d_head_rope),
        ("qk_head_dim", data["qk_head_dim"], cfg.head_dim),
        # config.json's head_dim is the rotary part (assumed.head_dim_is)
        ("head_dim", data["head_dim"], cfg.d_head_rope),
        ("v_head_dim", data["v_head_dim"], cfg.d_head_v),
        ("num_hidden_layers", data["num_hidden_layers"], cfg.n_layers),
        ("first_k_dense_replace", data["first_k_dense_replace"],
         cfg.n_dense_layers),
        ("moe_layer_freq", data["moe_layer_freq"], 1),
        ("intermediate_size", data["intermediate_size"], cfg.d_ff_dense),
        ("moe_intermediate_size", data["moe_intermediate_size"], cfg.ffn_dim),
        ("n_shared_experts x moe_intermediate_size",
         data["n_shared_experts"] * data["moe_intermediate_size"],
         cfg.d_ff_shared),
        ("n_routed_experts", data["n_routed_experts"], cfg.experts_here),
        ("router_width", assumed["router_width"], cfg.n_experts),
        ("num_experts_per_tok", data["num_experts_per_tok"],
         cfg.expert_top_k),
        ("norm_topk_prob", data["norm_topk_prob"], cfg.expert_norm_topk),
        ("routed_scaling_factor", data["routed_scaling_factor"],
         cfg.expert_gate_scale),
        ("scoring_func", data["scoring_func"], cfg.router_score),
        ("topk_method", data["topk_method"],
         "noaux_tc" if cfg.router_bias else "greedy"),
        ("n_group", data["n_group"], 1),
        ("topk_group", data["topk_group"], 1),
        ("hidden_act", data["hidden_act"], cfg.expert_activation),
        ("vocab_size", data["vocab_size"], cfg.vocab_size),
        ("max_position_embeddings", data["max_position_embeddings"],
         cfg.max_seq_len),
        ("rope_theta", data["rope_theta"], cfg.rope_theta),
        ("rope_scaling", data["rope_scaling"], None),
        # the program pairs halves: the same model (assumed.rope_pairing)
        ("rope_interleave", data["rope_interleave"], True),
        ("rms_norm_eps", data["rms_norm_eps"], cfg.norm_eps),
        ("tie_word_embeddings", data["tie_word_embeddings"], cfg.tied),
        ("attention_bias", data["attention_bias"], False),
        ("router_bias_rate", assumed["router_bias_rate"],
         cfg.router_bias_rate),
        ("router_aux_loss_coef", assumed["router_aux_loss_coef"],
         cfg.router_aux_weight),
        ("router_z_loss_coef", assumed["router_z_loss_coef"],
         cfg.router_z_weight),
        ("qk_norm", assumed["qk_norm"], cfg.qk_norm),
        ("dropless", assumed["dropless"], cfg.expert_capacity_factor is None),
        ("param_dtype", assumed["param_dtype"], cfg.param_dtype),
        ("compute_dtype", assumed["compute_dtype"], cfg.dtype),
    ])
