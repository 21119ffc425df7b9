"""The sizes an ``afmoe`` config file states (Trinity-Mini's
``config.json`` keys; under ``assumed`` what that file has no key for)
against the model its factory runs. ``num_experts`` is how many experts
the chip HOLDS (the cut: one expert-parallel rank's share); how many the
router scores is ``assumed.router_width``. ``num_hidden_layers`` counts
the leading dense layers that are run (``num_dense_layers``) with the
expert layers; ``layer_types`` is kept whole, and the layers the factory
runs are held to its entries from ``assumed.first_layer`` on. Keys the
program has no setting for are held to what its code does: no bias on a
projection, no group limit on the router's choice, plain RoPE, one
shared expert as wide as a routed one."""

from __future__ import annotations

from chipbench.sizes import _common

KINDS = {(True, True): "sliding_attention", (False, False): "full_attention"}


def check(data: dict, cfg) -> None:
    assumed = data["assumed"]
    n, first = data["num_hidden_layers"], assumed["first_layer"]
    kinds = [KINDS.get(cfg.layer_kind(i)) for i in range(cfg.n_layers)]
    every = data["global_attn_every_n_layers"]
    _common.compare([
        ("arch", data["arch"], "afmoe"),
        ("model_type", data["model_type"], "afmoe"),
        ("hidden_size", data["hidden_size"], cfg.d_model),
        ("num_attention_heads", data["num_attention_heads"], cfg.n_heads),
        ("num_key_value_heads", data["num_key_value_heads"], cfg.kv_heads),
        ("head_dim", data["head_dim"], cfg.head_dim),
        ("num_hidden_layers", n, cfg.n_layers),
        ("num_dense_layers", data["num_dense_layers"], cfg.n_dense_layers),
        ("first_layer", first, cfg.first_layer),
        ("layer_types", data["layer_types"][first:first + n], kinds),
        ("global_attn_every_n_layers", data["layer_types"],
         ["full_attention" if (i + 1) % every == 0 else "sliding_attention"
          for i in range(len(data["layer_types"]))]),
        ("sliding_window", data["sliding_window"], cfg.sliding_window),
        ("intermediate_size", data["intermediate_size"], cfg.d_ff_dense),
        ("moe_intermediate_size", data["moe_intermediate_size"], cfg.ffn_dim),
        ("num_shared_experts x moe_intermediate_size",
         data["num_shared_experts"] * data["moe_intermediate_size"],
         cfg.d_ff_shared),
        ("num_experts", data["num_experts"], cfg.experts_here),
        ("router_width", assumed["router_width"], cfg.n_experts),
        ("num_experts_per_tok", data["num_experts_per_tok"],
         cfg.expert_top_k),
        ("route_norm", data["route_norm"], cfg.expert_norm_topk),
        ("route_scale", data["route_scale"], cfg.expert_gate_scale),
        ("score_func", data["score_func"], cfg.router_score),
        ("load_balance_coeff", data["load_balance_coeff"],
         cfg.router_bias_rate),
        ("router_bias_rate", assumed["router_bias_rate"],
         cfg.router_bias_rate if cfg.router_bias else None),
        ("n_group", data["n_group"], 1),
        ("topk_group", data["topk_group"], 1),
        ("num_expert_groups", data["num_expert_groups"], 1),
        ("num_limited_groups", data["num_limited_groups"], 1),
        ("hidden_act", data["hidden_act"], cfg.expert_activation),
        ("vocab_size", data["vocab_size"], cfg.vocab_size),
        ("max_position_embeddings", data["max_position_embeddings"],
         cfg.max_seq_len),
        ("rope_theta", data["rope_theta"], cfg.rope_theta),
        ("rope_scaling", data["rope_scaling"], None),
        ("rms_norm_eps", data["rms_norm_eps"], cfg.norm_eps),
        ("tie_word_embeddings", data["tie_word_embeddings"], cfg.tied),
        ("mup_enabled", data["mup_enabled"],
         cfg.embed_scale == data["hidden_size"] ** 0.5),
        ("attention_gate", True, cfg.attn_gate),
        ("qk_norm", assumed["qk_norm"], cfg.qk_norm),
        ("post_norm", assumed["post_norm"], cfg.post_norm),
        ("router_aux_loss_coef", assumed["router_aux_loss_coef"],
         cfg.router_aux_weight),
        ("router_z_loss_coef", assumed["router_z_loss_coef"],
         cfg.router_z_weight),
        ("dropless", assumed["dropless"], cfg.expert_capacity_factor is None),
        ("param_dtype", assumed["param_dtype"], cfg.param_dtype),
        ("compute_dtype", assumed["compute_dtype"], cfg.dtype),
    ])
