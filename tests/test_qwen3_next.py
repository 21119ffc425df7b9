"""Qwen3-Next (a ``qwen3_next``-shaped model) on the normal path against
its plain reference (``chipbench/reference/qwen3_next.py``), at a
Qwen3-Next-shaped small size on the CPU: published layers 0 to 4 (D D D A
D: a whole period of Gated DeltaNet three to one gated attention layer,
and one layer behind it), hidden 64, attention 4 query heads on 2 key /
value heads of 32 of which the first 8 values are rotated, DeltaNet 4
query / key heads under 8 value heads of 16 behind a convolution over 4
positions, 16 SwiGLU experts of width 32, 4 a token by a softmax router,
gates renormalised, a gated shared expert 32 wide, the balance loss at
0.001. The parameters hold rank 1 of 4's experts (4 of the 16) unless a
test says otherwise.

Weights: the layer weights are drawn at ``SCALE`` x the program's N(0,
0.02), the router at 10 x that again, the zero-centred norms' weights
spread around 0 and the DeltaNet output norm's around 1, so that every
branch moves the logits, routing is uneven and no norm weight is silent.
Both sides compute in float32: the tolerances are float32 rounding grown
by the depth of the sums; a fault has to miss by 100 x that.
"""

from __future__ import annotations

import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import qwen3_next as reference
from ray_tpu import models
from ray_tpu.models import transformer
from ray_tpu.ops import linear_attention, moe

import _small_models as sm
from _small_models import highest_precision  # noqa: F401 (autouse)

TOL = 2e-5
T, E, K, RANKS = 64, 16, 4, 4
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def small(**kw):
    base = dict(
        n_layers=5, d_model=64, n_heads=4, n_kv_heads=2, d_head=32,
        kda_heads=8, kda_head_dim=16, linear_key_heads=4, d_ff=32,
        d_ff_shared=32, n_experts=E, expert_top_k=K, vocab_size=256,
        max_seq_len=T, experts_held=(1, RANKS), dtype="float32")
    base.update(kw)
    return models.qwen3_next_80b_a3b(**base)


def make(seed: int = 0, **kw):
    """(cfg, params, rows [2, T + 1])."""
    cfg, params, rows = sm.make(small, seed, tokens=T, as_drawn=(
        "ln1", "ln2", "q_norm", "k_norm", "o_norm", "A_log", "dt_bias"), **kw)
    spread = iter(jax.random.split(jax.random.PRNGKey(seed + 500), 64))

    def around(a):
        return a + 0.3 * jax.random.normal(next(spread), a.shape, a.dtype)

    layers = params["layers"]
    for name in ("ln1", "ln2"):
        layers[name]["w"] = around(layers[name]["w"])
    for name in ("q_norm", "k_norm"):
        layers["mha"][name] = around(layers["mha"][name])
    for name in ("o_norm", "A_log", "dt_bias"):     # not scaled: their own
        layers["gdn"][name] = around(layers["gdn"][name])
    return cfg, dict(params, final_norm={
        "w": around(params["final_norm"]["w"])}), rows


forward = sm.forward


# -- the preset ---------------------------------------------------------------

def test_preset_is_the_catalogs_config_key_by_key():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    pub, c = row["config"], models.qwen3_next_80b_a3b()
    assert pub["model_type"] == "qwen3_next"
    every = pub["full_attention_interval"]
    assert c.layer_mixers == tuple(
        "attn" if (i + 1) % every == 0 else "gdn"
        for i in range(pub["num_hidden_layers"]))
    assert c.layer_mixers.count("attn") == 12
    assert (c.n_layers, c.d_model, c.n_heads, c.kv_heads, c.head_dim) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["head_dim"]) == (48, 2048, 16, 2, 256)
    assert (c.kda_heads, c.linear_key_heads, c.kda_head_dim, c.kda_conv) == (
        pub["linear_num_value_heads"], pub["linear_num_key_heads"],
        pub["linear_key_head_dim"], pub["linear_conv_kernel_dim"]) == (
        32, 16, 128, 4)
    assert pub["linear_value_head_dim"] == c.kda_head_dim
    assert (c.rope_fraction, c.rope_theta, c.norm_eps, c.max_seq_len) == (
        pub["partial_rotary_factor"], pub["rope_theta"], pub["rms_norm_eps"],
        pub["max_position_embeddings"]) == (0.25, 1e7, 1e-6, 262144)
    assert (c.n_experts, c.expert_top_k, c.ffn_dim, c.d_ff_shared,
            c.expert_norm_topk, c.vocab_size, c.tied) == (
        pub["num_experts"], pub["num_experts_per_tok"],
        pub["moe_intermediate_size"], pub["shared_expert_intermediate_size"],
        pub["norm_topk_prob"], pub["vocab_size"],
        pub["tie_word_embeddings"]) == (512, 10, 512, 512, True, 151936,
                                        False)
    assert (pub["decoder_sparse_step"], pub["mlp_only_layers"],
            pub["use_sliding_window"], pub["rope_scaling"],
            pub["hidden_act"]) == (1, [], False, None, c.expert_activation)
    assert (c.n_dense_layers, c.sliding_window, c.layer_pattern) == (0, None,
                                                                     ())
    assert (c.qk_norm, c.attn_gate, c.norm_zero_centred,
            c.shared_expert_gate, c.router_score, c.router_bias) == (
        "head", True, True, True, "softmax", False)
    assert (c.router_aux_weight, c.router_z_weight,
            c.expert_capacity_factor) == (0.001, 0.0, None)
    # the cell's cut: one period, 32 of 512 experts, an eighth of the rows
    cut = models.qwen3_next_80b_a3b(n_layers=4, vocab_size=18992,
                                    experts_held=(0, 16))
    assert cut.layer_mixers == ("gdn", "gdn", "gdn", "attn")
    assert cut.num_params() == 625_667_136
    shapes = cut.shapes()["layers"]
    count = lambda t: sum(a.size for a in jax.tree.leaves(t))  # noqa: E731
    assert count(shapes["gdn"]) // 3 + 4096 * 2048 == 33_718_464
    assert count(shapes["mha"]) + 4096 * 2048 == 27_263_488


def test_a_kind_of_mixer_has_its_own_stack_and_the_period_is_four():
    cfg, params, _ = make()
    layers = params["layers"]
    assert cfg.layer_mixers == ("gdn", "gdn", "gdn", "attn", "gdn")
    assert [cfg.layer_kind(i) for i in range(5)] == [
        "gdn", "gdn", "gdn", (False, True), "gdn"]
    assert transformer._period(tuple(cfg.layer_kind(i) for i in range(5))) == 5
    assert transformer._period(tuple(
        small(n_layers=8).layer_kind(i) for i in range(8))) == 4
    assert layers["attn"]["wo"].shape == (5, 4, 32, 64)
    assert layers["gdn"]["wq"].shape == (4, 64, 4, 16)      # by KEY head
    assert layers["gdn"]["wv"].shape == layers["gdn"]["wz"].shape == (
        4, 64, 8, 16)
    assert layers["gdn"]["w_a"].shape == layers["gdn"]["A_log"].shape[:1] + (
        64, 8)
    assert layers["gdn"]["conv_v"].shape == (4, 4, 8, 16)
    assert layers["mha"]["wg"].shape == layers["mha"]["wq"].shape == (
        1, 64, 4, 32)
    assert layers["mha"]["k_norm"].shape == (1, 32)
    assert layers["mlp"]["shared_gate"].shape == (5, 64)
    assert "wo" not in layers["mha"] and "mla" not in layers
    fresh = models.init_params(jax.random.PRNGKey(0), cfg)
    assert float(jnp.abs(fresh["layers"]["ln1"]["w"]).max()) == 0.0
    assert float(jnp.abs(fresh["final_norm"]["w"]).max()) == 0.0
    assert float(jnp.abs(fresh["layers"]["mha"]["q_norm"]).max()) == 0.0
    assert float(fresh["layers"]["gdn"]["o_norm"].min()) == 1.0


# -- program = reference --------------------------------------------------------

@pytest.mark.parametrize("seed,kw", [
    (0, {}), (1, dict(experts_held=None)), (2, dict(n_layers=8)),
    (3, dict(scan_layers=False))],
    ids=["held", "uncut", "two-periods", "unrolled"])
def test_program_equals_reference_logits_and_loss(seed, kw):
    cfg, params, rows = make(seed, **kw)
    z = forward(params, rows[:, :-1], cfg)
    want = reference.forward(params, rows[:, :-1], cfg)
    assert float(want.std()) > 0.1
    assert float(jnp.abs(z - want).max()) < 5 * TOL
    assert float(sm.loss(params, rows, cfg)) == pytest.approx(
        float(reference.loss(params, rows, cfg)), abs=TOL)


def test_program_equals_reference_gradients_through_lm_loss():
    cfg, params, rows = make(4)
    got = sm.loss_metrics_and_grads(params, rows, cfg)[1]
    want = sm.grad(reference.loss, cfg)(params, rows)
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat) == len(jax.tree.leaves(want)) > 30
    for (path, a), b in zip(flat, jax.tree.leaves(want)):
        scale = float(jnp.abs(b).max())
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.abs(a - b).max()) < 1e-4 * (1 + scale), (
            jax.tree_util.keystr(path))


# -- every branch ------------------------------------------------------------------

def _zeroed(params, *names):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if tuple(k.key for k in path)[-len(names):]
        == names else a, params)


@pytest.mark.parametrize("names", [
    ("gdn", "wq"), ("gdn", "wk"), ("gdn", "wv"), ("gdn", "wz"),
    ("gdn", "w_a"), ("gdn", "dt_bias"), ("gdn", "w_beta"),
    ("gdn", "conv_q"), ("gdn", "conv_v"), ("gdn", "o_norm"),
    ("mha", "wq"), ("mha", "wk"), ("mha", "wv"), ("mha", "wg"),
    ("mha", "q_norm"), ("mha", "k_norm"), ("attn", "wo"),
    ("ln1", "w"), ("ln2", "w"), ("final_norm", "w"),
    ("mlp", "w_down"), ("mlp", "shared_w_down"), ("mlp", "shared_gate"),
    ("router", "w")], ids="/".join)
def test_a_leaf_moves_the_logits(names):
    """Zeroed, each leaf changes the logits: no branch is silent (a
    zero-centred norm's weight at 0 is the norm without its weight)."""
    cfg, params, rows = make(5)
    base = forward(params, rows[:, :-1], cfg)
    moved = forward(_zeroed(params, *names), rows[:, :-1], cfg)
    assert float(jnp.abs(moved - base).max()) > 100 * TOL, names


@pytest.mark.parametrize("change,sees", [
    (dict(rope_fraction=1.0), "the rotation over the whole head"),
    (dict(rope_fraction=0.5), "half a head rotated"),
    (dict(norm_zero_centred=False), "norms that scale by w"),
    (dict(attn_gate=False), "no gate on attention"),
    (dict(shared_expert_gate=False), "no gate on the shared expert"),
    (dict(expert_norm_topk=False), "gates not renormalised"),
    (dict(qk_norm=False), "no head norms")], ids=lambda c: str(c))
def test_the_comparison_sees(change, sees):
    cfg, params, rows = make(6)
    broken = replace(cfg, **change)
    want = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(forward(params, rows[:, :-1], cfg) - want).max()) \
        < 5 * TOL
    assert float(jnp.abs(forward(params, rows[:, :-1], broken) - want).max()) \
        > 100 * TOL, sees


def test_the_decay_and_the_balance_term_are_in_the_loss():
    cfg, params, rows = make(7)
    # no decay: A_log to -inf makes g = 0
    still = jax.tree_util.tree_map_with_path(
        lambda p, a: jnp.full_like(a, -1e9) if p[-1].key == "A_log" else a,
        params)
    base = forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(forward(still, rows[:, :-1], cfg) - base).max()) \
        > 100 * TOL
    no_term = replace(cfg, router_aux_weight=0.0)
    d = float(sm.loss(params, rows, cfg)
              - sm.loss(params, rows, no_term))
    assert 0.001 < d < 0.004           # 0.001 x a balance term of 1-4
    _, metrics = sm.lm_loss(params, rows, cfg)
    assert float(metrics["router_aux"]) * 0.001 == pytest.approx(d, rel=1e-3)


# -- the share ----------------------------------------------------------------------

def _block(x, lp, cfg, kind):
    from ray_tpu.ops.layers import rope_frequencies

    rope = rope_frequencies(int(cfg.head_dim * cfg.rope_fraction),
                            cfg.max_seq_len, theta=cfg.rope_theta)
    return transformer._block(x, lp, cfg, rope=rope, con=lambda t, *spec: t,
                              kind=kind)[0]


def _one_layer(x, lp, cfg, kind):
    return sm.jitted(_block, cfg, kind)(x, lp)


def _reference_layer(x, lp, cfg, mixer, first_held=0):
    return reference._jit_layer(
        x, lp, mixer, float(cfg.rope_theta),
        int(cfg.head_dim * cfg.rope_fraction), cfg.expert_top_k,
        first_held)[0]


@pytest.mark.parametrize("layer", [0, 3], ids=["deltanet", "attention"])
def test_the_ranks_routed_parts_and_the_gated_shared_expert_once_sum_to_the_uncut_layer(layer):
    """One layer on the same input: each rank's program block gives ``x +
    its mixer + its held experts' part + the gated shared expert``. What
    every rank computes alike (the mixer, the gated shared expert) counted
    ONCE, the four routed parts sum to the UNCUT reference's layer, which
    holds all 16 experts."""
    cfg, full, rows = make(8, experts_held=None)
    mixers = list(cfg.layer_mixers)
    x = full["embed"]["tokens"][rows[:, :-1]] * 10.0
    lp = reference.stack_layer(full["layers"], mixers, layer)
    kind = cfg.layer_kind(layer)
    alike = sm.ranks_parts_sum_to_the_uncut_layer(
        x, lp, cfg, RANKS,
        lambda x, lp: _reference_layer(x, lp, cfg, mixers[layer]),
        lambda x, lp, cfg: _one_layer(x, lp, cfg, kind), TOL)
    assert float(jnp.abs(alike - x).max()) > 1000 * TOL


# -- a training step ------------------------------------------------------------------

def test_a_step_moves_every_leaf_by_adamw_and_reports_the_counters():
    cfg, params, rows = make(9)
    lr = 1e-3
    opt = sm.adamw(lr, weight_decay=0.0)
    state = sm.train_state(params, opt)
    new, metrics = sm.train_step(cfg, opt)(state, {"tokens": rows})
    for (path, before), after in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree.leaves(new["params"])):
        moved = jnp.abs(after - before)
        if jax.tree_util.keystr(path) == "['embed']['tokens']":
            moved = moved[jnp.unique(rows[:, :-1])]     # the rows read
        # AdamW's first update is lr x the gradient's sign
        assert 0.5 * lr < float(moved.mean()) <= 1.001 * lr, \
            jax.tree_util.keystr(path)
    assert float(metrics["attn_gate_mean"]) == pytest.approx(0.5, abs=0.1)
    assert float(metrics["moe_shared_gate_mean"]) == pytest.approx(0.5,
                                                                   abs=0.1)
    assert float(metrics["kda_log_decay_min"]) < -1.0
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0
    assert float(metrics["router_aux"]) > 1.0
    # accumulation keeps the counters
    _, accumulated = sm.train_step(cfg, opt, accum_steps=2)(
        state, {"tokens": rows})
    for name in ("attn_gate_mean", "moe_shared_gate_mean"):
        assert float(accumulated[name]) == pytest.approx(
            float(metrics[name]), abs=0.05)


def _forward_and_aux(params, tokens, cfg):
    return models.forward(params, tokens, cfg, return_aux=True)


def test_the_shared_gates_mean_is_zero_where_nobody_pays_for_it():
    ks = jax.random.split(jax.random.PRNGKey(10), 5)
    # feature 0 is 1 in every token: a weight on it alone shuts every gate
    x = jax.random.normal(ks[0], (2, 8, 16)).at[..., 0].set(1.0)
    w_gate, w_up = (jax.random.normal(k, (16, 32)) for k in ks[1:3])
    w_down = jax.random.normal(ks[3], (32, 16))
    w_share = jax.random.normal(ks[4], (16,))
    plain = moe.shared_expert(x, w_gate, w_up, w_down)
    share = jax.nn.sigmoid(x @ w_share)
    out, mean = moe.gated_shared_expert(x, w_gate, w_up, w_down, w_share)
    np.testing.assert_allclose(out, plain * share[..., None], rtol=1e-5,
                               atol=1e-6)
    assert float(mean) == pytest.approx(float(share.mean()), rel=1e-6)
    shut = jnp.zeros(16).at[0].set(-100.0)
    out, mean = moe.gated_shared_expert(x, w_gate, w_up, w_down, shut)
    assert float(mean) < 1e-30 and float(jnp.abs(out).max()) < 1e-30
    # in the model: the mean over the layers, about a half at a seeded init
    cfg, params, rows = make(10)
    _, aux = sm.jitted(_forward_and_aux, cfg)(params, rows[:, :-1])
    assert 0.2 < float(aux["moe_shared_gate_mean"]) < 0.8


# -- what the config refuses ------------------------------------------------------------

@pytest.mark.parametrize("changes,named", [
    (dict(kv_latent=16, d_head_nope=8, d_head_rope=8, d_head_v=8,
          qk_norm=False, n_kv_heads=4, attn_gate=False, rope_fraction=1.0),
     "'gdn' beside latent attention"),
    (dict(layer_mixers=("gdn", "kda", "gdn", "attn", "gdn")),
     "'kda' beside attention that is not latent"),
    (dict(layer_mixers=("gdn", "mamba", "gdn", "attn", "gdn")),
     "names other than"),
    (dict(linear_key_heads=3), "linear_key_heads=3"),
    (dict(kda_heads=6, linear_key_heads=3), "Gated DeltaNet heads"),
    (dict(rope_fraction=0.3), "rope_fraction=0.3"),
    (dict(rope_fraction=0.0), "rope_fraction=0.0"),
    (dict(d_ff_shared=0), "shared_expert_gate gates the shared expert"),
    (dict(arch="gpt2"), "arch='llama'"),
    (dict(layer_pattern=((False, True),)), "a layer_pattern"),
])
def test_what_the_config_refuses(changes, named):
    with pytest.raises(ValueError, match=named):
        models.init_params(jax.random.PRNGKey(0), replace(small(), **changes))


def test_linear_key_heads_belong_to_gdn_layers_alone():
    with pytest.raises(ValueError, match="linear_key_heads"):
        models.init_params(jax.random.PRNGKey(0), models.kimi_linear_48b_a3b(
            n_layers=5, linear_key_heads=16))
    with pytest.raises(ValueError, match="linear_key_heads"):
        models.init_params(jax.random.PRNGKey(0), models.tiny(
            arch="llama", linear_key_heads=2))


def test_no_serving_path_runs_this_model():
    """A Gated DeltaNet layer keeps a recurrent state and its
    convolution's last positions, not keys and values: ``refuse_decode``
    names the fields ahead of every other refusal, each new field has its
    own, and ``LLMEngine`` calls it before its own refusal of experts."""
    import inspect

    from ray_tpu.llm import engine

    cfg = small()
    with pytest.raises(NotImplementedError,
                       match="layer_mixers .*linear_key_heads 4.*Gated "
                             "DeltaNet"):
        models.init_kv_cache(cfg, 1, 32)
    with pytest.raises(NotImplementedError, match="layer_mixers"):
        models.decode_step(None, jnp.zeros((1, 1), jnp.int32),
                           {"pos": jnp.zeros((), jnp.int32)}, cfg)
    plain = models.tiny(arch="llama")
    for field, value in (("rope_fraction", 0.5), ("norm_zero_centred", True),
                         ("attn_gate", True)):
        with pytest.raises(NotImplementedError, match=field):
            transformer.refuse_decode(replace(plain, **{field: value}))
    with pytest.raises(NotImplementedError, match="shared_expert_gate|"
                                                  "d_ff_shared"):
        transformer.refuse_decode(replace(
            plain, n_experts=4, expert_capacity_factor=None, d_ff_shared=8,
            shared_expert_gate=True))
    source = inspect.getsource(engine.LLMEngine.__init__)
    assert source.index("tfm.refuse_decode(c)") < source.index("MoE decode")


# -- partitioning -------------------------------------------------------------------------

def test_the_mixers_stacks_go_through_partition_specs_on_a_virtual_mesh():
    from jax.sharding import PartitionSpec as P

    cfg, params, rows = make(11, experts_held=None)
    specs, _ = sm.sharded_loss_is_the_unsharded(cfg, params, rows, TOL)
    gdn, by_head = specs["layers"]["gdn"], P(None, None, "tensor", None)
    assert gdn["wq"] == gdn["wz"] == gdn["conv_k"] == gdn["conv_v"] == by_head
    assert gdn["A_log"] == gdn["dt_bias"] == P(None, "tensor")
    assert gdn["w_a"] == gdn["w_beta"] == P(None, None, "tensor")
    assert gdn["o_norm"] is None
    mha = specs["layers"]["mha"]
    assert mha["wq"] == mha["wg"] == mha["wk"] == by_head
    assert mha["q_norm"] is None
    assert specs["layers"]["attn"]["wo"] == P(None, "tensor", None, None)
    assert specs["layers"]["mlp"]["shared_gate"] is None


# -- scopes -------------------------------------------------------------------------------

def test_the_scopes_are_on_the_instructions():
    """DeltaNet layers under ``attn_linear`` with ``attn_qkv``,
    ``kda_conv``, ``kda_gate``, ``attn_core``, ``attn_out`` inside; the
    attention layer under ``attn_full`` with its parts and its gate; the
    shared expert's gate inside ``moe_shared``."""
    cfg, params, rows = make(12)
    opt = sm.adamw(1e-3, weight_decay=0.0)      # the step's above: its trace
    text = sm.train_step(cfg, opt).lower(
        sm.train_state(params, opt), {"tokens": rows}).as_text(
            debug_info=True)
    for path in ("attn/attn_linear/attn_qkv", "attn/attn_linear/kda_conv",
                 "attn/attn_linear/kda_gate", "attn/attn_linear/attn_core",
                 "attn/attn_linear/attn_out", "attn/attn_full/attn_qkv",
                 "attn/attn_full/attn_pos", "attn/attn_full/attn_gqa",
                 "attn/attn_full/attn_core", "attn/attn_full/attn_gate",
                 "attn/attn_full/attn_out", "moe/moe_shared/logistic",
                 "moe/moe_router"):
        assert path in text, path
    assert "attn_window" not in text and "mla_latent" not in text
    assert transformer.MIXERS[:3] == ("attn", "kda", "gdn")
    assert linear_attention.SCOPES == ("kda_conv", "kda_gate")
    for module in (linear_attention, moe):
        assert module.__file__ in transformer.SCOPE_FILES
