"""Kimi Linear (KDA layers and NoPE latent-attention layers mixed, one
mixer a layer) on the normal path against its plain reference
(``chipbench/reference/kimi_linear.py``: the delta rule token by token),
at a Kimi-shaped small size on the CPU: hidden 64, KDA with 4 heads of 16
and a convolution over 4 positions, latent attention with 4 heads of 16 +
8 (ONE shared key part, nothing rotated) against values of 16 from a
32-wide latent, a leading dense layer (SwiGLU 96) with a KDA mixer, 8
SwiGLU experts of width 32, 3 a token by a sigmoid router whose bias only
the choice sees, gates renormalised and scaled by 2.446, a shared expert
48 wide, no router loss. Five layers are the published layers 1-5 (K | K
K A K: the cell's cut); 27 are the published kinds (K | six periods of K
K A K, then K A unrolled behind the scan). The parameters hold rank 1 of
4's experts unless a test says otherwise.

Weights as in ``tests/test_kanana2.py``: the matrices at ``SCALE`` x the
program's N(0, 0.02) (the router 10 x that again, its bias 5 x), so that
every branch moves the logits; the gates' ``A_log`` / ``dt_bias`` and the
convolutions as ``init_params`` draws them. Both sides compute in
float32.
"""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from chipbench.reference import _common
from chipbench.reference import kimi_linear as reference
from ray_tpu import models
from ray_tpu.models import transformer
from ray_tpu.ops import linear_attention, moe

import _small_models as sm
from _small_models import highest_precision  # noqa: F401 (autouse)

TOL = 5e-5
T, E, K, RANKS = 80, 8, 3, 4
AS_DRAWN = ("A_log", "dt_bias", "conv_q", "conv_k", "conv_v", "o_norm",
            "kv_norm")


def small(**kw):
    base = dict(
        n_layers=5, d_model=64, n_heads=4, d_ff=32, kv_latent=32,
        d_head_nope=16, d_head_rope=8, d_head_v=16, kda_heads=4,
        kda_head_dim=16, d_ff_dense=96, d_ff_shared=48, n_experts=E,
        expert_top_k=K, vocab_size=256, max_seq_len=128,
        experts_held=(1, RANKS), dtype="float32")
    base.update(kw)
    return models.kimi_linear_48b_a3b(**base)


init = jax.jit(models.init_params, static_argnums=1)


def make(seed: int = 0, **kw):
    """(cfg, params, rows [2, T + 1])."""
    return sm.make(small, seed, tokens=T, init=init,
                   as_drawn=("ln1", "ln2") + AS_DRAWN, **kw)


# jitted: eagerly, the chunked rule's loops are thousands of dispatches
forward = sm.forward


def reference_loss(params, rows, cfg):
    return _common.next_token_loss(
        reference.forward(params, rows[:, :-1], cfg), rows)


# -- the preset ---------------------------------------------------------------

def test_preset_is_kimi_linear_as_published():
    c = models.kimi_linear_48b_a3b()
    transformer._check_config(c)
    data = spec.load_json("chipbench", "configs",
                          "kimi-linear-48b-a3b-ep32.json")
    published = {**data, **data["published"]}
    linear = data["linear_attn_config"]
    assert (c.n_layers, c.n_dense_layers, c.d_model, c.n_heads, c.kv_heads,
            c.kv_latent, c.d_head_nope, c.d_head_rope, c.d_head_v,
            c.d_ff_dense, c.ffn_dim, c.n_experts, c.expert_top_k,
            c.vocab_size, c.max_seq_len) == tuple(published[k] for k in (
        "num_hidden_layers", "first_k_dense_replace", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "num_experts",
        "num_experts_per_token", "vocab_size", "model_max_length"))
    assert (c.n_layers, c.n_experts, c.vocab_size) == (27, 256, 163840)
    assert (c.kda_heads, c.kda_head_dim, c.kda_conv) == (
        linear["num_heads"], linear["head_dim"],
        linear["short_conv_kernel_size"]) == (32, 128, 4)
    kinds = [c.layer_kind(i) for i in range(27)]
    assert [i + 1 for i, k in enumerate(kinds) if k == "kda"] == \
        linear["kda_layers"]
    assert [i + 1 for i, k in enumerate(kinds) if k == (False, False)] == \
        linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert (c.latent_rope, c.d_ff_shared, c.router_score, c.router_bias,
            c.router_bias_rate, c.expert_gate_scale, c.expert_norm_topk,
            c.router_aux_weight, c.expert_capacity_factor, c.norm_eps,
            c.tied, c.arch) == (False, 1024, "sigmoid", True, 1e-3, 2.446,
                                True, 0.0, None, 1e-5, False, "llama")
    # the scan: the 26 expert layers are six periods of K K A K, then K A
    scan = tuple(kinds[1:])
    assert transformer._period(scan) == 4 and scan[:4] == (
        "kda", "kda", (False, False), "kda") and scan[24:] == (
        "kda", (False, False))
    assert models.kimi_linear_48b_a3b(n_layers=5).layer_mixers == (
        "kda", "kda", "kda", "attn", "kda")


def test_the_period_is_read_off_the_kinds():
    period = transformer._period
    w, f = (True, True), (False, False)
    assert period((None,) * 16) == 1 and period((f, w, w, w)) == 4
    assert period((f, w, w, w) * 13) == 4 and period(("kda",)) == 1
    assert period(("kda", "kda", f, "kda")) == 4
    assert period(("kda", f) * 3 + ("kda",)) == 2


def test_a_kind_of_mixer_has_its_own_stack():
    cfg, params, _ = make()
    layers, dense = params["layers"], params["dense_layers"]
    assert set(layers) == {"attn", "kda", "mla", "ln1", "ln2", "router", "mlp"}
    assert set(dense) == {"attn", "kda", "ln1", "ln2", "mlp"}
    assert set(layers["attn"]) == {"wo"} and layers["attn"]["wo"].shape == (
        4, 4, 16, 64)
    assert {a.shape[0] for a in jax.tree.leaves(layers["kda"])} == {3}
    assert {a.shape[0] for a in jax.tree.leaves(layers["mla"])} == {1}
    assert set(layers["mla"]) == {"wq", "wkv_a", "kv_norm", "wkv_b"}
    assert {name: a.shape[1:] for name, a in layers["kda"].items()} == {
        "wq": (64, 4, 16), "wk": (64, 4, 16), "wv": (64, 4, 16),
        "conv_q": (4, 4, 16), "conv_k": (4, 4, 16), "conv_v": (4, 4, 16),
        "f_a": (64, 16), "f_b": (16, 4, 16), "dt_bias": (4, 16),
        "A_log": (4,), "w_beta": (64, 4), "g_a": (64, 16),
        "g_b": (16, 4, 16), "o_norm": (16,)}
    # the gates' init: A in [1, 16], a step in [0.001, 0.1], taps in +-1/2
    raw = init(jax.random.PRNGKey(3), cfg)
    kda = raw["layers"]["kda"]
    rate, step = jnp.exp(kda["A_log"]), jax.nn.softplus(kda["dt_bias"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 0.1001
    assert 0.4 < float(jnp.abs(kda["conv_q"]).max()) <= 0.5
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))


# -- the whole model ------------------------------------------------------------

@pytest.mark.parametrize("seed,held", [(0, (1, 4)), (1, None)])
def test_program_equals_reference_logits_and_loss(seed, held):
    cfg, params, rows = make(seed, experts_held=held)
    z = forward(params, rows[:, :-1], cfg)
    z_ref = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.std(z_ref)) > 0.05
    assert float(jnp.abs(z - z_ref).max()) < TOL
    loss, metrics = sm.lm_loss(params, rows, cfg)
    assert float(loss) == pytest.approx(
        float(reference_loss(params, rows, cfg)), abs=TOL)
    # the most negative sum of 64 log-decays: at most 16 x 0.1 a token
    assert -64 * 1.6 * 1.3 < float(metrics["kda_log_decay_min"]) < -0.06


def test_program_equals_reference_gradients_through_lm_loss():
    cfg, params, rows = make(0)
    got = sm.loss_metrics_and_grads(params, rows, cfg)[1]
    want = sm.grad(reference_loss, cfg)(params, rows)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == len(jax.tree.leaves(params))
    for (path, a), b in zip(flat_got, flat_want):
        name = "/".join(k.key for k in path)
        if name.endswith("router/b"):
            assert float(jnp.abs(a).max()) == 0.0       # the choice alone
            continue
        assert float(jnp.abs(b).max()) > 0, name
        assert float(jnp.abs(a - b).max()) < 3e-3 * max(
            1e-3, float(jnp.abs(b).max())), name


def test_whole_periods_in_the_scan_and_the_rest_behind_it_are_the_reference():
    """11 layers: K | two periods of K K A K in a scan, then K K in line
    (the published 27 are K | six periods, then K A: the same code, trained
    a step below). Scanned and unrolled are one model, the reference's."""
    cfg, params, rows = make(2, n_layers=11)
    want = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(forward(params, rows[:, :-1], cfg) - want).max()) < TOL
    other = forward(params, rows[:, :-1], replace(cfg, scan_layers=False))
    assert float(jnp.abs(other - want).max()) < TOL


@pytest.mark.parametrize("n_layers", [27, 11])
def test_a_stack_splits_into_whole_periods_and_the_rest(n_layers):
    cfg = small(n_layers=n_layers)
    params = cfg.shapes()
    kinds = tuple(cfg.layer_kind(i) for i in range(1, n_layers))
    periods, left = divmod(n_layers - 1, 4)
    whole, rest = jax.eval_shape(
        lambda stack: transformer._split_stack(cfg, stack, kinds, 4),
        params["layers"])
    assert whole["kda"]["wq"].shape[:2] == (periods, 3)
    assert whole["mla"]["wq"].shape[:2] == (periods, 1)
    assert whole["ln1"]["w"].shape[:2] == (periods, 4)
    assert rest["ln1"]["w"].shape[0] == left == 2
    assert rest["kda"]["wq"].shape[0] == kinds[-2:].count("kda")


def test_every_branch_moves_the_logits():
    cfg, params, rows = make(3)
    base = forward(params, rows[:, :-1], cfg)

    def moved(stack, *names):
        sub = params[stack]
        for name in names[:-1]:
            sub = sub[name]
        zeroed = jax.tree_util.tree_map_with_path(
            lambda path, a: a * 0 if [k.key for k in path][-len(names):]
            == list(names) and stack in [k.key for k in path] else a, params)
        return float(jnp.abs(forward(zeroed, rows[:, :-1], cfg) - base).max())

    for stack, names in (("layers", ("kda", "wv")), ("layers", ("kda", "g_b")),
                         ("layers", ("mla", "wkv_b")),
                         ("layers", ("attn", "wo")),
                         ("dense_layers", ("kda", "wq")),
                         ("dense_layers", ("mlp", "w_down")),
                         ("layers", ("mlp", "shared_w_down")),
                         ("layers", ("mlp", "w_down"))):
        assert moved(stack, *names) > 100 * TOL, (stack, names)


# -- the share --------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _one_layer(x, lp, cfg, kind, dense=False):
    return transformer._block(x, lp, cfg, rope=None, con=lambda t, *spec: t,
                              kind=kind, dense=dense)[0]


def _reference_layer(x, lp, cfg, mixer, dense=False, first_held=0):
    return jax.jit(reference._layer, static_argnums=tuple(range(2, 10)))(
        x, lp, mixer, dense, cfg.d_head_nope, cfg.kv_latent,
        float(cfg.norm_eps), cfg.expert_top_k, float(cfg.expert_gate_scale),
        first_held)


@pytest.mark.parametrize("layer", [0, 2])
def test_the_ranks_routed_parts_sum_to_the_uncut_layer(layer):
    """One expert layer (0: a KDA layer, 2: the latent one) on the same
    input: each rank's program block gives ``h + its mixer + its held
    experts' part + the shared expert``. What every rank computes alike
    (the mixer and the shared expert) counted ONCE, the four routed parts
    sum to the UNCUT reference's layer, which holds all 8 experts."""
    cfg, full, rows = make(4, experts_held=None)
    mixers = cfg.layer_mixers[1:]
    x = full["embed"]["tokens"][rows[:, :-1]] * 10.0
    lp = reference.stack_layer(full["layers"], mixers, layer)
    kind = cfg.layer_kind(1 + layer)
    alike = sm.ranks_parts_sum_to_the_uncut_layer(
        x, lp, cfg, RANKS,
        lambda x, lp: _reference_layer(x, lp, cfg, mixers[layer]),
        lambda x, lp, cfg: _one_layer(x, lp, cfg, kind), TOL)
    bare = dict(lp, mlp=dict(lp["mlp"], w_down=lp["mlp"]["w_down"] * 0,
                             shared_w_down=lp["mlp"]["shared_w_down"] * 0),
                attn={"wo": lp["attn"]["wo"] * 0})
    assert float(jnp.abs(_reference_layer(x, bare, cfg, mixers[layer])
                         - x).max()) < TOL
    assert float(jnp.abs(alike - x).max()) > 1000 * TOL


def test_the_dense_layer_has_a_kda_mixer_and_is_the_references():
    cfg, params, rows = make(5)
    x = params["embed"]["tokens"][rows[:, :-1]] * 10.0
    lp = reference.stack_layer(params["dense_layers"], cfg.layer_mixers[:1], 0)
    assert "kda" in lp and "mla" not in lp
    want = _reference_layer(x, lp, cfg, "kda", dense=True)
    got = _one_layer(x, lp, cfg, "kda", dense=True)
    assert float(jnp.abs(got - want).max()) < 5 * TOL
    assert float(jnp.abs(want - x).max()) > 1000 * TOL


# -- a training step ----------------------------------------------------------------

def _published_kinds_step(lr: float = 3e-4):
    """(the jitted train step, its state, its batch, the parameters) of the
    published 27 kinds as rank 31 of a 32-way share: ONE trace and lowering
    for the case that runs the step and the case that reads its text."""
    cfg = small(dtype="bfloat16", n_layers=27, d_ff=16, d_ff_dense=32,
                d_ff_shared=16, experts_held=(31, 32), n_experts=32)
    params = init(jax.random.PRNGKey(6), cfg)
    rows = jax.random.randint(jax.random.PRNGKey(7), (2, 65), 0, 256)
    opt = sm.adamw(lr, weight_decay=0.1)
    return (sm.train_step(cfg, opt), sm.train_state(params, opt),
            {"tokens": rows}, params)


def test_a_step_moves_every_leaf_by_adamw_and_the_bias_by_its_rule():
    """``make_train_step`` with the cell's options (defaults: remat, scan,
    bfloat16 compute, unchunked loss) on the published 27 kinds (the scan
    over six periods and the two layers behind it) as rank 31 of a 32-way
    share (the five-layer cut trains through ``JaxTrainer.fit`` in
    ``tests/chipbench/test_chipbench_kimi_linear.py``): every leaf but
    the router's bias moves by about the learning rate (AdamW's first
    update), the bias by exactly its rate, and the new counter is in the
    metrics."""
    lr = 3e-4
    step, state, batch, params = _published_kinds_step(lr)
    new, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["kda_log_decay_min"]) < 0
    assert "moe_expert_counts" not in metrics
    before = jax.tree_util.tree_leaves_with_path(params)
    after = jax.tree.leaves(new["params"])
    for (path, a), b in zip(before, after):
        name = "/".join(k.key for k in path)
        change = np.abs(np.asarray(b, np.float64) - np.asarray(a, np.float64))
        if name.endswith("router/b"):
            assert set(np.unique(change.round(7))) <= {0.0, 1e-3}, name
            assert change.max() == pytest.approx(1e-3, rel=1e-3)
        else:
            assert 0.2 * lr < change.mean() < 1.5 * lr, (name, change.mean())


# -- what is refused, by name -----------------------------------------------------

@pytest.mark.parametrize("changes,named", [
    (dict(layer_mixers=("kda", "kda", "kda", "attn")), "4 names for n_layers=5"),
    (dict(layer_mixers=("kda",) * 4 + ("mamba",)), "names other than"),
    (dict(layer_pattern=((False, False),)), "latent attention .* layer_pattern"),
    (dict(kda_heads=0), "kda_heads, kda_head_dim or kda_conv < 1"),
    (dict(kda_heads=2), "KDA heads .* that are not attention's"),
    (dict(kda_head_dim=32), "KDA heads .* that are not attention's"),
    (dict(kv_latent=None, d_head_nope=0, d_head_rope=0, d_head_v=0,
          latent_rope=True), "attention that is not latent"),
    (dict(kv_latent=None, d_head_nope=0, d_head_rope=0, d_head_v=0),
     "latent_rope=False describes latent attention"),
    (dict(n_dense_layers=5), "n_dense_layers are the first"),
    (dict(expert_capacity_factor=1.25), "the dropless path's"),
])
def test_what_the_config_refuses(changes, named):
    with pytest.raises(ValueError, match=named):
        transformer._check_config(small(**changes))


def test_a_layer_pattern_still_takes_neither_a_mixer_list_nor_latent_attention():
    """The (windowed, rope) ``layer_pattern`` is refused with both, as
    before: the mixer list is what latent attention and a leading dense
    stack now take."""
    plain = dict(kv_latent=None, d_head_nope=0, d_head_rope=0, d_head_v=0,
                 latent_rope=True, layer_mixers=())
    with pytest.raises(ValueError, match="n_dense_layers are the first"):
        models.init_params(jax.random.PRNGKey(0), small(
            **plain, layer_pattern=((False, True),)))
    with pytest.raises(ValueError, match="layer_mixers does not run with a "
                                         "layer_pattern"):
        transformer._check_config(replace(
            small(), layer_pattern=((False, True),), kv_latent=None,
            d_head_nope=0, d_head_rope=0, d_head_v=0, latent_rope=True,
            n_dense_layers=0, d_ff_dense=None))


def test_no_serving_path_runs_this_model():
    """A KDA layer keeps a recurrent state and its convolution's last
    positions, not keys and values: ``refuse_decode`` names the fields
    ahead of every other refusal, and ``LLMEngine`` calls it before its
    own refusal of experts."""
    import inspect

    from ray_tpu.llm import engine

    cfg = small()
    with pytest.raises(NotImplementedError, match="layer_mixers .*kda_heads 4"):
        models.init_kv_cache(cfg, 1, 32)
    with pytest.raises(NotImplementedError, match="layer_mixers"):
        models.decode_step(None, jnp.zeros((1, 1), jnp.int32),
                           {"pos": jnp.zeros((), jnp.int32)}, cfg)
    dense_kda = replace(cfg, n_experts=0, n_dense_layers=0, d_ff_dense=None,
                        d_ff_shared=0, router_score="softmax",
                        router_bias=False, router_bias_rate=0.0,
                        expert_gate_scale=1.0, experts_held=None,
                        expert_capacity_factor=1.25)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        transformer.refuse_decode(dense_kda)
    nope = replace(dense_kda, layer_mixers=())
    with pytest.raises(NotImplementedError, match="kv_latent"):
        transformer.refuse_decode(nope)
    with pytest.raises(NotImplementedError, match="latent_rope"):
        transformer.refuse_decode(replace(
            nope, kv_latent=0, latent_rope=False))
    source = inspect.getsource(engine.LLMEngine.__init__)
    assert source.index("tfm.refuse_decode(c)") < source.index("MoE decode")


# -- partitioning -----------------------------------------------------------------

def test_the_mixers_stacks_go_through_partition_specs_on_a_virtual_mesh():
    from jax.sharding import PartitionSpec as P

    cfg, params, rows = make(8, experts_held=None)
    specs, _ = sm.sharded_loss_is_the_unsharded(cfg, params, rows, TOL)
    for stack in ("layers", "dense_layers"):
        kda = specs[stack]["kda"]
        by_head = P(None, None, "tensor", None)
        assert kda["wq"] == kda["conv_k"] == kda["f_b"] == kda["g_b"] == by_head
        assert kda["A_log"] == P(None, "tensor")
        assert kda["dt_bias"] == P(None, "tensor", None)
        assert kda["f_a"] is None and kda["o_norm"] is None
        assert specs[stack]["attn"]["wo"] == P(None, "tensor", None, None)
    assert specs["layers"]["mla"]["wkv_b"] == P(None, None, "tensor", None)


# -- scopes -----------------------------------------------------------------------

def test_the_new_scopes_are_on_the_instructions():
    """``attn_linear`` and inside it ``attn_qkv``, ``kda_conv``,
    ``kda_gate``, ``attn_core``, ``attn_out``, in both stacks; the latent
    layer keeps ``attn_full`` / ``mla_latent`` / ``attn_core`` and opens no
    ``attn_pos`` (nothing is rotated). Read off the step of the published
    27 kinds, which the step's own case has traced."""
    step, state, batch, _ = _published_kinds_step()
    text = step.lower(state, batch).as_text(debug_info=True)
    for path in ("attn/attn_linear/attn_qkv", "attn/attn_linear/kda_conv",
                 "attn/attn_linear/kda_gate", "attn/attn_linear/attn_core",
                 "attn/attn_linear/attn_out", "attn/attn_full/mla_latent",
                 "attn/attn_full/attn_core", "attn/attn_full/attn_qkv",
                 "moe/moe_shared", "optimizer/sign"):
        assert path in text, path
    assert "attn_pos" not in text and "attn_window" not in text
    assert transformer.ATTN_SCOPES == ("attn_full", "attn_window",
                                       "attn_linear")
    assert linear_attention.SCOPES == ("kda_conv", "kda_gate")
    assert linear_attention.__file__ in transformer.SCOPE_FILES
