"""The sizes a ``phi4flash`` config file states (Phi-4-mini-flash-
reasoning's ``config.json`` keys, ``model_type`` ``phi4flash``; under
``assumed`` what that file has no key for: the four Mamba sizes, the
biases, the differential form) against the model its factory runs.
``num_hidden_layers`` layers from the published layer ``first_layer`` run;
which mixer each is follows from its PUBLISHED number ``l`` and
``mb_per_layer`` 2 (``l`` even: Mamba-1 up to the middle layer 16, a
memory unit past it; ``l`` odd: attention up to 17, windowed up to 15,
cross-attention past it). Keys the program has no setting for are held to
what its code does: no bias on the MLP or the head, ``silu``, no dropout.
``parameters`` is held line by line to the factory's own leaves, its
``total`` to ``cfg.num_params()`` and ``published_depth_total`` to the
factory at the published depth and vocabulary."""

from __future__ import annotations

import math

from chipbench import spec
from chipbench.sizes import _common


def _mixer(l: int, layers: int) -> str:
    if l % 2 == 0:
        return "ssm1" if l <= layers // 2 else "gmu"
    return "attn" if l <= layers // 2 + 1 else "cross"


def _per_layer(shapes, name: str):
    """Parameters ONE layer holds in the stack's subtree ``name`` (None
    where the model has no such subtree)."""
    sub = shapes["layers"].get(name)
    if sub is None:
        return None
    import jax

    return sum(math.prod(a.shape[1:]) for a in jax.tree.leaves(sub))


def check(data: dict, cfg) -> None:
    assumed, stated = data["assumed"], data["parameters"]
    published = data["published"]
    n, first = data["num_hidden_layers"], cfg.first_layer
    numbers = range(first, first + n)
    layers = [_mixer(l, published["num_hidden_layers"]) for l in numbers]
    windows = [data["sliding_window"]
               if l % 2 and l <= published["num_hidden_layers"] // 2 - 1
               else None for l in numbers]
    ran = [cfg.layer_kind(i) for i in range(cfg.n_layers)]
    shapes = cfg.shapes()
    size = lambda *names: sum(_per_layer(shapes, name) or 0 for name in names)
    whole = spec.model_config(
        data, n_layers=published["num_hidden_layers"], first_layer=0,
        vocab_size=published["vocab_size"])
    _common.compare([
        ("arch", data["arch"], "phi4flash"),
        ("model_type", data["model_type"], "phi4flash"),
        ("hidden_size", data["hidden_size"], cfg.d_model),
        ("num_hidden_layers", n, cfg.n_layers),
        ("first_layer", data["factory_kwargs"]["first_layer"], first),
        ("mb_per_layer", data["mb_per_layer"], 2),
        ("the layers' mixers", layers, list(cfg.layer_mixers)),
        ("the attention layers' windows", windows,
         [cfg.sliding_window if isinstance(kind, tuple) and kind[0] else None
          for kind in ran]),
        ("positions", assumed["positions"].startswith("none"),
         not cfg.attn_rope and not any(
             kind[1] for kind in ran if isinstance(kind, tuple))),
        ("num_attention_heads", data["num_attention_heads"], cfg.n_heads),
        ("num_key_value_heads", data["num_key_value_heads"], cfg.kv_heads),
        ("head_dim", data["hidden_size"] // data["num_attention_heads"],
         cfg.head_dim),
        ("intermediate_size", data["intermediate_size"], cfg.ffn_dim),
        ("hidden_act", data["hidden_act"], "silu"),
        ("mlp_bias", data["mlp_bias"], False),
        ("lm_head_bias", data["lm_head_bias"], False),
        ("embd_pdrop", data["embd_pdrop"], 0),
        ("resid_pdrop", data["resid_pdrop"], 0),
        ("layer_norm_eps", data["layer_norm_eps"], cfg.norm_eps),
        ("LayerNorm with a bias", True, cfg.layer_norm),
        ("sliding_window", data["sliding_window"], cfg.sliding_window),
        ("max_position_embeddings", data["max_position_embeddings"],
         cfg.max_seq_len),
        ("tie_word_embeddings", data["tie_word_embeddings"], cfg.tied),
        ("vocab_size", data["vocab_size"], cfg.vocab_size),
        ("mamba_d_state", assumed["mamba_d_state"], cfg.ssm_state),
        ("mamba_d_conv", assumed["mamba_d_conv"], cfg.kda_conv),
        ("mamba_expand", assumed["mamba_expand"], cfg.ssm_expand),
        ("mamba_dt_rank", assumed["mamba_dt_rank"], cfg.ssm_dt_rank),
        ("mamba_dt_rank is ceil(hidden_size / 16)",
         assumed["mamba_dt_rank"], math.ceil(data["hidden_size"] / 16)),
        ("the convolution's bias", True, cfg.ssm_conv_bias),
        ("attention_bias", assumed["attention_bias"], cfg.attn_bias),
        ("differential_attention", assumed["differential_attention"],
         cfg.diff_attn),
        ("param_dtype", assumed["param_dtype"], cfg.param_dtype),
        ("compute_dtype", assumed["compute_dtype"], cfg.dtype),
        ("parameters.mamba1_mixer", stated["mamba1_mixer"], size("ssm1")),
        ("parameters.attention_mixer", stated["attention_mixer"],
         size("mha", "attn")),
        ("parameters.memory_unit_mixer", stated["memory_unit_mixer"],
         size("gmu")),
        ("parameters.cross_mixer", stated["cross_mixer"],
         size("cross", "attn")),
        ("parameters.mlp", stated["mlp"], size("mlp")),
        ("parameters.layer_norms", stated["layer_norms"], size("ln1", "ln2")),
        ("parameters.mamba1_layer", stated["mamba1_layer"],
         size("ssm1", "mlp", "ln1", "ln2")),
        ("parameters.attention_layer", stated["attention_layer"],
         size("mha", "attn", "mlp", "ln1", "ln2")),
        ("parameters.memory_unit_layer", stated["memory_unit_layer"],
         size("gmu", "mlp", "ln1", "ln2")),
        ("parameters.cross_layer", stated["cross_layer"],
         size("cross", "attn", "mlp", "ln1", "ln2")),
        ("parameters.layers_14_to_19", stated["layers_14_to_19"],
         sum(stated[f"{kind}_layer"] for kind in (
             {"ssm1": "mamba1", "attn": "attention", "gmu": "memory_unit",
              "cross": "cross"}[m] for m in cfg.layer_mixers))),
        ("parameters.embedding_and_head", stated["embedding_and_head"],
         cfg.vocab_size * cfg.d_model),
        ("parameters.final_norm", stated["final_norm"], 2 * cfg.d_model),
        ("parameters.total", stated["total"], cfg.num_params()),
        ("parameters.bytes_at_16_a_parameter",
         stated["bytes_at_16_a_parameter"], 16 * cfg.num_params()),
        ("parameters.published_depth_total", stated["published_depth_total"],
         whole.num_params()),
    ])
