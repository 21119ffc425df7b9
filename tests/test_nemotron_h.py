"""The ``nemotron_h`` arch (NVIDIA-Nemotron-3-Nano-30B-A3B) at test size on
the CPU, float32, seeded weights: the program (``ray_tpu.models``: a stack
of SINGLE-SUBLAYER blocks, Mamba-2 state-space mixers, NoPE GQA attention,
experts without a gate under a biased sigmoid router) against the plain
reference (``chipbench/reference/nemotron_h.py``: the recurrence token by
token) for logits, loss and the gradient of every leaf; the 16 ranks'
shares against the uncut layer; the train step's counters and the
router's bias; what ``_check_config`` and ``refuse_decode`` refuse by
name; the scopes the mixer's parts run under. One small model a file
(``tests/_small_models.py``): a case costs its distinct compiles."""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

import _small_models as sm
from _small_models import highest_precision  # noqa: F401  (autouse)
from chipbench.reference import _common
from chipbench.reference import nemotron_h as reference
from ray_tpu import models
from ray_tpu.models import transformer
from ray_tpu.ops import linear_attention, moe, state_space

T, E, K, RANKS = 40, 16, 3, 16
TOL = 2e-5


def small(**kw):
    base = dict(
        n_layers=9, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        kda_heads=4, kda_head_dim=8, ssm_state=16, ssm_groups=2,
        ssm_chunk=16, d_ff=24, d_ff_shared=40, n_experts=E, expert_top_k=K,
        vocab_size=128, max_seq_len=T, experts_held=(1, 2), dtype="float32")
    base.update(kw)
    return models.nemotron_3_nano_30b_a3b(**base)


def make(seed: int = 0, **kw):
    """(cfg, params, rows [2, T + 1]): every matrix at 5 x its draw, the
    mixer's own small leaves as they are drawn."""
    return sm.make(small, seed, tokens=T, as_drawn=(
        "ln1", "ln2", "o_norm", "A_log", "dt_bias", "D", "conv_w", "conv_b"),
        **kw)


def _reference_loss(params, rows, cfg):
    return _common.next_token_loss(reference.forward(params, rows[:, :-1],
                                                     cfg), rows)


# -- program against reference ---------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_logits_loss_and_every_leafs_gradient_are_the_references(seed):
    cfg, params, rows = make(seed)
    assert cfg.layer_mixers == ("ssm", "ffn", "ssm", "ffn", "ssm", "attn",
                                "ffn", "ssm", "ffn")
    z_p = sm.forward(params, rows[:, :-1], cfg)
    z_r = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(z_r).max()) > 0.3
    assert float(jnp.abs(z_p - z_r).max()) < TOL
    (loss, metrics), grads = sm.loss_metrics_and_grads(params, rows, cfg)
    want, want_grads = jax.value_and_grad(_reference_loss)(params, rows, cfg)
    assert float(loss) == pytest.approx(float(want), abs=TOL)
    assert float(metrics["router_aux"]) > 0      # reported, weighted by 0

    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    theirs = jax.tree.leaves(want_grads)
    assert len(flat) == len(theirs) == len(jax.tree.leaves(params))
    for (path, mine), ref in zip(flat, theirs):
        name = "/".join(k.key for k in path)
        if name == "layers/router/b":            # seen by the choice alone
            assert float(jnp.abs(mine).max()) == 0 == float(
                jnp.abs(ref).max())
            continue
        size = float(jnp.abs(ref).max())
        assert size > 0, name
        assert float(jnp.abs(mine - ref).max()) < 1e-4 * size + 1e-7, name


def test_a_layer_is_one_sublayer_and_holds_its_own_leaves_alone():
    cfg, params, _ = make()
    layers = params["layers"]
    n = {name: jax.tree.leaves(sub)[0].shape[0]
         for name, sub in layers.items()}
    assert n == {"attn": 5, "ln1": 5, "mha": 1, "ssm": 4, "ln2": 4,
                 "router": 4, "mlp": 4}
    assert set(layers["mlp"]) == {"w_up", "w_down", "shared_w_up",
                                  "shared_w_down"}        # no gate's leaf
    assert cfg.single_sublayer and cfg.linear_mixer == "ssm"
    assert (cfg.layers_with("ssm"), cfg.layers_with("ffn"),
            cfg.layers_with("attn")) == ((0, 2, 4, 7), (1, 3, 6, 8), (5,))
    kinds = tuple(cfg.layer_kind(i) for i in range(9))
    assert kinds[5] == (False, False) and kinds[0] == "ssm"   # NoPE
    assert transformer._period(kinds) == 9       # nine in one scan step
    # the published count, at the published widths and the cell's cut
    full = models.nemotron_3_nano_30b_a3b(
        n_layers=9, vocab_size=16384, experts_held=(0, 16))
    assert full.num_params() == 666_963_456
    assert models.nemotron_3_nano_30b_a3b().layer_mixers.count("ssm") == 23
    # unrolled, the layers are the scan's
    cfg, params, rows = make()
    loose = replace(cfg, scan_layers=False)
    assert float(jnp.abs(sm.forward(params, rows[:, :-1], cfg) - sm.forward(
        params, rows[:, :-1], loose)).max()) < TOL


def _block(x, lp, cfg, kind):
    return transformer._block(x, lp, cfg, rope=None,
                              con=lambda t, *spec: t, kind=kind)[0]


def test_the_16_ranks_parts_and_the_shared_expert_once_sum_to_the_uncut_layer():
    """One expert layer on the same input: each rank's program block gives
    ``x + its held expert's part + the shared expert``. What every rank
    computes alike counted ONCE, the sixteen routed parts sum to the UNCUT
    reference's layer, which holds all 16 experts."""
    cfg, full, rows = make(8, experts_held=None)
    kinds = list(cfg.layer_mixers)
    x = full["embed"]["tokens"][rows[:, :-1]] * 10.0
    lp = reference.stack_layer(full["layers"], kinds, 1)
    assert set(lp) == {"ln2", "router", "mlp"}

    def reference_layer(x, lp):
        return reference._jit_layer(x, lp, "ffn", cfg.ssm_groups,
                                    cfg.ssm_state, K, 2.5, 0)

    alike = sm.ranks_parts_sum_to_the_uncut_layer(
        x, lp, cfg, RANKS, reference_layer,
        lambda x, lp, cfg: sm.jitted(_block, cfg, "ffn")(x, lp), TOL)
    assert float(jnp.abs(alike - x).max()) > 1000 * TOL     # the shared one


# -- a training step -------------------------------------------------------------

def test_a_step_reports_the_counters_and_moves_the_bias_by_its_rule():
    cfg, params, rows = make(3)
    opt = sm.adamw(1e-3, weight_decay=0.0)
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    new, metrics = sm.train_step(cfg, opt)(state, {"tokens": rows})
    for name in ("kda_log_decay_min", "ssm_step_mean", "moe_held_share",
                 "moe_full_buffer", "moe_load_max", "router_bias_absmax",
                 "moe_bias_swapped"):
        assert metrics[name].shape == (), name
    assert "moe_expert_counts" not in metrics
    # the seeded init's step: log-uniform in [0.001, 0.1]
    assert 0.005 < float(metrics["ssm_step_mean"]) < 0.05
    assert float(metrics["kda_log_decay_min"]) < 0
    assert 0 < float(metrics["moe_held_share"]) < 1     # the FFN layers' mean
    b0, b1 = (s["params"]["layers"]["router"]["b"] for s in (state, new))
    assert b0.shape == (4, E)
    moved = jnp.abs(b1 - b0)
    assert float(moved.max()) == pytest.approx(cfg.router_bias_rate, rel=1e-4)
    assert float(jnp.abs(b1.sum(-1) - b0.sum(-1)).max()) < 1e-2
    # every other leaf moved
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(state["params"])[0],
            jax.tree.leaves(new["params"])):
        assert float(jnp.abs(a - b).max()) > 0, path


# -- what is refused, by name ------------------------------------------------------

@pytest.mark.parametrize("changes,named", [
    (dict(ssm_state=0), r"layer_mixers\[0\] = 'ssm' needs ssm_state"),
    (dict(ssm_groups=3), r"layer_mixers\[0\] = 'ssm' needs .*divide"),
    (dict(post_norm=True), r"layer_mixers\[1\] = 'ffn' needs an FFN of "
                           "experts alone"),
    (dict(layer_mixers=("ssm", "ffn", "gdn") + ("ffn",) * 6),
     "more than one kind of linear mixer"),
    (dict(layer_mixers=("ssm", "mlp") + ("ffn",) * 7), "names other than"),
    (dict(kda_heads=8), "heads .* that are not attention's"),
    (dict(expert_capacity_factor=1.25), "expert_gated=False are the "
                                        "dropless path's"),
    (dict(shared_expert_gate=True), "shared_expert_gate does not run with "
                                    "experts that have no gate"),
    (dict(layer_mixers=(), attn_rope=False), "attn_rope=False describes"),
])
def test_check_config_refuses_by_name(changes, named):
    with pytest.raises(ValueError, match=named):
        transformer._check_config(small(**changes))


def test_refuse_decode_names_the_state_space_layer_and_the_single_sublayers():
    cfg = small()
    for refused in (lambda: models.init_kv_cache(cfg, 1, 8),
                    lambda: transformer.refuse_decode(cfg)):
        with pytest.raises(NotImplementedError) as e:
            refused()
        said = str(e.value)
        assert "state-space" in said and "single-sublayer" in said
        assert "ssm_state 16" in said and "'ffn'" in said


# -- scopes ------------------------------------------------------------------------

def test_the_mixers_parts_run_under_the_linear_mixers_scope_names():
    cfg, params, rows = make()
    text = jax.jit(jax.grad(sm.program_loss), static_argnums=2).lower(
        params, rows, cfg).as_text(debug_info=True)
    inside = "attn/attn_linear/"
    for part in ("attn_qkv", "kda_conv", "kda_gate", "attn_core",
                 "attn_core/ssm_carry", "attn_out"):
        assert inside + part in text, part
    assert "attn/attn_full/attn_core" in text
    assert "attn_full/attn_pos" not in text         # nothing is rotated
    for scope in ("attn_norm", "mlp_norm", "moe/moe_shared",
                  "moe/moe_experts", "moe/moe_router"):
        assert scope in text, scope
    assert "/mlp/" not in text                      # no dense FFN anywhere
    assert state_space.SCOPES == ("ssm_carry",)
    assert linear_attention.SCOPES == ("kda_conv", "kda_gate")
    for module in (linear_attention, moe, state_space):
        assert module.__file__ in transformer.SCOPE_FILES
    assert transformer.SCOPE_FILES[4] == state_space.__file__


def test_partition_specs_mirror_the_tree_and_a_mesh_gives_the_same_loss():
    cfg, params, rows = make(5)
    specs, _ = sm.sharded_loss_is_the_unsharded(cfg, params, rows, 5e-5)
    assert specs["layers"]["ssm"]["w_z"] is not None
    assert specs["layers"]["ssm"]["w_xbc"] is None
