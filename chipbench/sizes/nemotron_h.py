"""The sizes a ``nemotron_h`` config file states (NVIDIA-Nemotron-3-Nano-
30B-A3B's ``config.json`` keys, ``model_type`` ``nemotron_h``; under
``assumed`` what that file has no key for) against the model its factory
runs. ``n_routed_experts`` is how many experts the chip HOLDS (the cut:
one expert-parallel rank's share); how many the router scores is
``assumed.router_width``. ``hybrid_override_pattern`` is kept whole: its
first ``num_hidden_layers`` letters are the layers that run (``M`` a
state-space mixer, ``E`` experts, ``*`` attention), each ONE sublayer.
``intermediate_size`` (the dense MLP's, which no letter of this pattern
uses) and ``expand`` (the model's code takes the mixer's inner width as
``mamba_num_heads * mamba_head_dim`` = 4,096, not ``expand *
hidden_size`` = 5,376) are read by nothing here. Keys the program has no setting for are
held to what its code does: no bias on any projection, no window, no
positions (``rope_theta`` and ``partial_rotary_factor`` are read by no
line of the model's code). ``parameters.total`` is every parameter the
chip holds, held to the factory's own count."""

from __future__ import annotations

from chipbench.sizes import _common

KINDS = {"M": "ssm", "E": "ffn", "*": "attn"}


def check(data: dict, cfg) -> None:
    assumed = data["assumed"]
    n = data["num_hidden_layers"]
    layers = [KINDS[letter] for letter in data["hybrid_override_pattern"][:n]]
    heads, width = data["mamba_num_heads"], data["mamba_head_dim"]
    _common.compare([
        ("arch", data["arch"], "nemotron_h"),
        ("model_type", data["model_type"], "nemotron_h"),
        ("hidden_size", data["hidden_size"], cfg.d_model),
        ("num_hidden_layers", n, cfg.n_layers),
        ("hybrid_override_pattern", layers, list(cfg.layer_mixers)),
        ("one sublayer a layer", True, cfg.single_sublayer),
        ("num_attention_heads", data["num_attention_heads"], cfg.n_heads),
        ("num_key_value_heads", data["num_key_value_heads"], cfg.kv_heads),
        ("head_dim", data["head_dim"], cfg.head_dim),
        ("attention_bias", data["attention_bias"], False),
        ("sliding_window", data["sliding_window"], cfg.sliding_window),
        ("positions", assumed["positions"].startswith("none"),
         not cfg.attn_rope),
        ("mamba_num_heads", heads, cfg.kda_heads),
        ("mamba_head_dim", width, cfg.kda_head_dim),
        ("the mixer's inner width", heads * width,
         cfg.kda_heads * cfg.kda_head_dim),
        ("ssm_state_size", data["ssm_state_size"], cfg.ssm_state),
        ("n_groups", data["n_groups"], cfg.ssm_groups),
        ("conv_kernel", data["conv_kernel"], cfg.kda_conv),
        ("use_conv_bias", data["use_conv_bias"], cfg.ssm_conv_bias),
        ("chunk_size", data["chunk_size"], cfg.ssm_chunk),
        ("mamba_hidden_act", data["mamba_hidden_act"], "silu"),
        ("mamba_proj_bias", data["mamba_proj_bias"], False),
        ("use_bias", data["use_bias"], False),
        ("mlp_bias", data["mlp_bias"], False),
        ("mlp_hidden_act", data["mlp_hidden_act"], cfg.expert_activation),
        ("experts without a gate projection", True, not cfg.expert_gated),
        ("moe_intermediate_size", data["moe_intermediate_size"], cfg.ffn_dim),
        ("moe_shared_expert_intermediate_size",
         data["moe_shared_expert_intermediate_size"] * data["n_shared_experts"],
         cfg.d_ff_shared),
        ("n_routed_experts", data["n_routed_experts"], cfg.experts_here),
        ("router_width", assumed["router_width"], cfg.n_experts),
        ("num_experts_per_tok", data["num_experts_per_tok"],
         cfg.expert_top_k),
        ("n_group", data["n_group"], 1),
        ("topk_group", data["topk_group"], 1),
        ("norm_topk_prob", data["norm_topk_prob"], cfg.expert_norm_topk),
        ("routed_scaling_factor", data["routed_scaling_factor"],
         cfg.expert_gate_scale),
        ("router_score", assumed["router_score"], cfg.router_score),
        ("router_bias", assumed["router_bias"], cfg.router_bias),
        ("router_bias_rate", assumed["router_bias_rate"],
         cfg.router_bias_rate),
        ("vocab_size", data["vocab_size"], cfg.vocab_size),
        ("max_position_embeddings", data["max_position_embeddings"],
         cfg.max_seq_len),
        ("norm_eps", data["norm_eps"], cfg.norm_eps),
        ("layer_norm_epsilon", data["layer_norm_epsilon"], cfg.norm_eps),
        ("tie_word_embeddings", data["tie_word_embeddings"], cfg.tied),
        ("router_aux_loss_coef", assumed["router_aux_loss_coef"],
         cfg.router_aux_weight),
        ("router_z_loss_coef", assumed["router_z_loss_coef"],
         cfg.router_z_weight),
        ("dropless", assumed["dropless"], cfg.expert_capacity_factor is None),
        ("param_dtype", assumed["param_dtype"], cfg.param_dtype),
        ("compute_dtype", assumed["compute_dtype"], cfg.dtype),
        ("parameters.total", data["parameters"]["total"], cfg.num_params()),
    ])
