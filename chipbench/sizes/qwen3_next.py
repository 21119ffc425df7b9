"""The sizes a ``qwen3_next`` config file states (Qwen3-Next-80B-A3B's
``config.json`` keys, ``model_type`` ``qwen3_next``; under ``assumed``
what that file has no key for) against the model its factory runs.
``num_experts`` is how many experts the chip HOLDS (the cut: one
expert-parallel rank's share); how many the router scores is
``assumed.router_width``. Layer ``i`` (from 0) is full attention where
``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet; every
layer has experts (``mlp_only_layers`` empty, ``decoder_sparse_step`` 1),
so the dense ``intermediate_size`` is read by no layer and by nothing
here (the file keeps the published number). Keys the program has no setting for are held to what
its code does: no bias, no window, no rope scaling. ``parameters.total``
is every parameter the chip holds, held to the factory's own count."""

from __future__ import annotations

from chipbench.sizes import _common


def check(data: dict, cfg) -> None:
    assumed = data["assumed"]
    every = data["full_attention_interval"]
    mixers = ["attn" if (i + 1) % every == 0 else "gdn"
              for i in range(data["num_hidden_layers"])]
    _common.compare([
        ("arch", data["arch"], "qwen3_next"),
        ("model_type", data["model_type"], "qwen3_next"),
        ("hidden_size", data["hidden_size"], cfg.d_model),
        ("num_hidden_layers", data["num_hidden_layers"], cfg.n_layers),
        ("full_attention_interval", mixers, list(cfg.layer_mixers)),
        ("num_attention_heads", data["num_attention_heads"], cfg.n_heads),
        ("num_key_value_heads", data["num_key_value_heads"], cfg.kv_heads),
        ("head_dim", data["head_dim"], cfg.head_dim),
        ("partial_rotary_factor", data["partial_rotary_factor"],
         cfg.rope_fraction),
        ("rope_theta", data["rope_theta"], cfg.rope_theta),
        ("rope_scaling", data["rope_scaling"], None),
        ("use_sliding_window", data["use_sliding_window"],
         cfg.sliding_window is not None),
        ("attention_gate", assumed["attention_gate"], cfg.attn_gate),
        ("qk_norm", assumed["qk_norm"], cfg.qk_norm),
        ("zero_centred_norms", assumed["zero_centred_norms"],
         cfg.norm_zero_centred),
        ("linear_num_value_heads", data["linear_num_value_heads"],
         cfg.kda_heads),
        ("linear_num_key_heads", data["linear_num_key_heads"],
         cfg.linear_key_heads),
        ("linear_key_head_dim", data["linear_key_head_dim"],
         cfg.kda_head_dim),
        ("linear_value_head_dim", data["linear_value_head_dim"],
         cfg.kda_head_dim),
        ("linear_conv_kernel_dim", data["linear_conv_kernel_dim"],
         cfg.kda_conv),
        ("decoder_sparse_step", data["decoder_sparse_step"], 1),
        ("mlp_only_layers", data["mlp_only_layers"],
         list(range(cfg.n_dense_layers))),
        ("moe_intermediate_size", data["moe_intermediate_size"], cfg.ffn_dim),
        ("shared_expert_intermediate_size",
         data["shared_expert_intermediate_size"], cfg.d_ff_shared),
        ("shared_expert_gate", assumed["shared_expert_gate"],
         cfg.shared_expert_gate),
        ("num_experts", data["num_experts"], cfg.experts_here),
        ("router_width", assumed["router_width"], cfg.n_experts),
        ("num_experts_per_tok", data["num_experts_per_tok"],
         cfg.expert_top_k),
        ("norm_topk_prob", data["norm_topk_prob"], cfg.expert_norm_topk),
        ("router_score", assumed["router_score"], cfg.router_score),
        ("hidden_act", data["hidden_act"], cfg.expert_activation),
        ("vocab_size", data["vocab_size"], cfg.vocab_size),
        ("max_position_embeddings", data["max_position_embeddings"],
         cfg.max_seq_len),
        ("rms_norm_eps", data["rms_norm_eps"], cfg.norm_eps),
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
