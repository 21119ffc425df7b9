"""The sizes a ``smallthinker`` config file states (SmallThinker's
``config.json`` keys; under ``assumed`` what that file has no key for)
against the model its factory runs. ``moe_num_primary_experts`` is how
many experts the chip HOLDS (the cut: one expert-parallel rank's share);
how many the router scores is ``assumed.router_width``. The two layout
lists are kept whole; the layers the factory runs are held to their
first entries. Keys the program has no setting for are held to what its
code does: no bias, no QK-norm, plain RoPE."""

from __future__ import annotations

from chipbench.sizes import _common


def check(data: dict, cfg) -> None:
    assumed = data["assumed"]
    n = data["num_hidden_layers"]
    kinds = [cfg.layer_kind(i) or (False, True) for i in range(cfg.n_layers)]
    _common.compare([
        ("arch", data["arch"], "smallthinker"),
        ("hidden_size", data["hidden_size"], cfg.d_model),
        ("head_dim", data["head_dim"], cfg.head_dim),
        ("moe_ffn_hidden_size", data["moe_ffn_hidden_size"], cfg.ffn_dim),
        ("num_attention_heads", data["num_attention_heads"], cfg.n_heads),
        ("num_key_value_heads", data["num_key_value_heads"], cfg.kv_heads),
        ("num_hidden_layers", n, cfg.n_layers),
        ("vocab_size", data["vocab_size"], cfg.vocab_size),
        ("max_position_embeddings", data["max_position_embeddings"],
         cfg.max_seq_len),
        ("rope_theta", data["rope_theta"], cfg.rope_theta),
        ("rope_scaling", data["rope_scaling"], None),
        ("rms_norm_eps", data["rms_norm_eps"], cfg.norm_eps),
        ("tie_word_embeddings", data["tie_word_embeddings"], cfg.tied),
        ("moe_num_primary_experts", data["moe_num_primary_experts"],
         cfg.experts_here),
        ("router_width", assumed["router_width"], cfg.n_experts),
        ("moe_num_active_primary_experts",
         data["moe_num_active_primary_experts"], cfg.expert_top_k),
        ("norm_topk_prob", data["norm_topk_prob"], cfg.expert_norm_topk),
        ("moe_primary_router_apply_softmax",
         data["moe_primary_router_apply_softmax"], True),
        ("sliding_window_size", data["sliding_window_size"],
         cfg.sliding_window),
        ("sliding_window_layout", data["sliding_window_layout"][:n],
         [int(windowed) for windowed, _ in kinds]),
        ("rope_layout", data["rope_layout"][:n],
         [int(rope) for _, rope in kinds]),
        ("expert_activation", assumed["expert_activation"],
         cfg.expert_activation),
        ("router_input", assumed["router_input"], cfg.router_input),
        ("qk_norm", assumed["qk_norm"], cfg.qk_norm),
        ("attention_bias", assumed["attention_bias"], False),
        ("dropless", assumed["dropless"], cfg.expert_capacity_factor is None),
        ("router_aux_loss_coef", assumed["router_aux_loss_coef"],
         cfg.router_aux_weight),
        ("router_z_loss_coef", assumed["router_z_loss_coef"],
         cfg.router_z_weight),
        ("param_dtype", assumed["param_dtype"], cfg.param_dtype),
        ("compute_dtype", assumed["compute_dtype"], cfg.dtype),
    ])
