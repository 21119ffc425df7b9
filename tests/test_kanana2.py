"""kanana-2 (a DeepSeek-V3-shaped model) on the normal path against its
plain reference (``chipbench/reference/kanana2.py``), at a kanana-shaped
small size on the CPU: one leading dense layer (SwiGLU 96) and two expert
layers, hidden 64, latent attention with 4 heads of 16 + 8 (rotary, ONE
key for all heads) against values of 16 from a 32-wide latent, 8 SwiGLU
experts of width 32, 3 a token by a sigmoid router whose bias only the
choice sees, gates renormalised and scaled by 2.448, a shared expert 48
wide, no router loss. The parameters hold rank 1 of 4's experts (2 of the
8) unless a test says otherwise.

Weights: as in ``tests/test_smallthinker.py``, the layer weights are
drawn at ``SCALE`` x the program's N(0, 0.02), the router at 10 x that
again and the bias at 25 x, so that every branch moves the logits, routing
is uneven and the bias changes the choice of many tokens. Both sides
compute in float32: the tolerances are float32 rounding grown by the
depth of the sums; a fault has to miss by 100 x that.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from chipbench.reference import _common
from chipbench.reference import kanana2 as reference
from ray_tpu import models
from ray_tpu.models import mixers, transformer
from ray_tpu.ops import moe

import _small_models as sm
from _small_models import highest_precision  # noqa: F401 (autouse)

TOL = 2e-5
T, E, K, RANKS = 64, 8, 3, 4


def small(**kw):
    base = dict(
        n_layers=3, d_model=64, n_heads=4, d_ff=32, kv_latent=32,
        d_head_nope=16, d_head_rope=8, d_head_v=16, d_ff_dense=96,
        d_ff_shared=48, n_experts=E, expert_top_k=K, vocab_size=256,
        max_seq_len=T, experts_held=(1, RANKS), dtype="float32")
    base.update(kw)
    return models.kanana_2_30b_a3b(**base)


def make(seed: int = 0, **kw):
    """(cfg, params, rows [2, T + 1])."""
    return sm.make(small, seed, tokens=T, bias_scale=5.0,
                   as_drawn=("ln1", "ln2", "kv_norm"), **kw)


def reference_loss(params, rows, cfg):
    return _common.next_token_loss(
        reference.forward(params, rows[:, :-1], cfg), rows)


# -- the preset ---------------------------------------------------------------

def test_preset_is_kanana_2_as_published():
    c = models.kanana_2_30b_a3b()
    data = spec.load_json("chipbench", "configs",
                          "kanana-2-30b-a3b-ep8.json")
    published = {**data, **data["published"]}
    want = tuple(published[k] for k in (
        "num_hidden_layers", "first_k_dense_replace", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "qk_head_dim",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "vocab_size", "max_position_embeddings"))
    assert want[-4:] == (128, 6, 128256, 32768)
    assert (c.n_layers, c.n_dense_layers, c.d_model, c.n_heads, c.kv_heads,
            c.kv_latent, c.d_head_nope, c.d_head_rope, c.d_head_v,
            c.head_dim, c.d_ff_dense, c.ffn_dim, c.n_experts, c.expert_top_k,
            c.vocab_size, c.max_seq_len) == want
    assert c.d_ff_shared == published["n_shared_experts"] * 768 == 1536
    assert (c.router_score, c.router_bias, c.router_bias_rate,
            c.expert_gate_scale, c.expert_norm_topk, c.router_aux_weight,
            c.router_z_weight, c.expert_capacity_factor, c.rope_theta,
            c.norm_eps, c.tied, c.arch) == (
        "sigmoid", True, 1e-3, 2.448, True, 0.0, 0.0, None, 1e6, 1e-6, False,
        "llama")
    assert c.n_scan_layers == 47 and c.experts_held is None


# What ``init_params`` gave and ``lm_loss`` read on the PARENT commit
# (4334b8c), seed 7 and rows of seed 5: (leaves, sum of |weights|, loss).
UNCHANGED = {
    "tiny": (lambda: models.tiny(), 16, 1948.299386, 5.550256729125977),
    "tiny_llama": (lambda: models.tiny(arch="llama"), 12, 2600.072762,
                   5.568553924560547),
    "tiny_moe": (lambda: models.tiny_moe(), 13, 6540.100032,
                 5.605009078979492),
    "olmoe": (lambda: models.olmoe_1b_7b(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32,
        n_experts=8, expert_top_k=3, vocab_size=256, max_seq_len=64),
        15, 2879.652478, 5.5973005294799805),
    "smallthinker": (lambda: models.smallthinker_21b_a3b(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_head=32, d_ff=32,
        n_experts=8, expert_top_k=3, vocab_size=256, max_seq_len=64,
        sliding_window=16, experts_held=(1, 4)), 13, 2977.941016,
        5.5987067222595215),
}


@pytest.mark.parametrize("name", list(UNCHANGED))
def test_every_other_factory_keeps_its_weights_and_its_loss(name):
    """The new leaves draw from keys of their own (``init_params``): what
    a seed gave every model before, it gives now."""
    factory, leaves, total, loss = UNCHANGED[name]
    cfg = factory()
    params = models.init_params(jax.random.PRNGKey(7), cfg)
    assert len(jax.tree.leaves(params)) == leaves
    assert sum(float(np.abs(np.asarray(a, np.float64)).sum())
               for a in jax.tree.leaves(params)) == pytest.approx(
        total, rel=1e-9)
    rows = jax.random.randint(jax.random.PRNGKey(5), (2, 33), 0, 256)
    assert float(sm.lm_loss(params, rows, cfg)[0]) == \
        pytest.approx(loss, rel=1e-6)
    assert "dense_layers" not in params


def test_the_tree_keeps_the_names_the_benchmarks_controls_walk():
    cfg, params, _ = make()
    layers = params["layers"]
    assert set(params) == {"embed", "dense_layers", "layers", "final_norm",
                           "lm_head"}
    assert layers["attn"]["wo"].shape == (2, 4, 16, 64)
    assert layers["mlp"]["w_down"].shape == (2, 2, 32, 64)      # [L, Eh, F, D]
    assert layers["mlp"]["shared_w_down"].shape == (2, 48, 64)
    assert layers["router"]["w"].shape == (2, 64, E)
    assert layers["router"]["b"].shape == (2, E)
    assert params["dense_layers"]["mlp"]["w_down"].shape == (1, 96, 64)
    assert params["dense_layers"]["attn"]["wkv_a"].shape == (1, 64, 32 + 8)
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    # the controls hit what they name: a layer's down matrices, the shared
    # expert's among them; the routed experts' alone
    dropped = _common.drop_layer(params, 1)["layers"]
    for leaf in (dropped["attn"]["wo"], dropped["mlp"]["w_down"],
                 dropped["mlp"]["shared_w_down"]):
        assert not bool(jnp.any(leaf[1])) and bool(jnp.any(leaf[0]))
    experts = _common.drop_experts(params, 0)["layers"]["mlp"]
    assert not bool(jnp.any(experts["w_down"][0]))
    assert bool(jnp.array_equal(experts["shared_w_down"],
                                layers["mlp"]["shared_w_down"]))
    # the leaf ``step_moves_the_weights`` reads at the cell's size is the
    # FIRST of the smallest in ``jax.tree.leaves`` order: the dense stack's
    # latent norm (one AdamW moves), ahead of the router's bias
    shapes = spec.model_config(spec.load_json(
        "chipbench", "configs", "kanana-2-30b-a3b-ep8.json")).shapes()
    paths = jax.tree_util.tree_leaves_with_path(shapes)
    first = min(paths, key=lambda p: int(np.prod(p[1].shape)))
    assert jax.tree_util.keystr(first[0]) == \
        "['dense_layers']['attn']['kv_norm']" and first[1].shape == (1, 512)
    assert shapes["layers"]["router"]["b"].shape == (4, 128)


# -- program against reference ----------------------------------------------------

def test_every_branch_moves_the_logits():
    cfg, params, rows = make()
    base = reference.forward(params, rows[:, :-1], cfg)
    for stack, keys in (("layers", ("attn", "wo")),
                        ("layers", ("mlp", "w_down")),
                        ("layers", ("mlp", "shared_w_down")),
                        ("dense_layers", ("mlp", "w_down")),
                        ("dense_layers", ("attn", "wkv_b"))):
        group, leaf = keys
        cut = dict(params, **{stack: dict(params[stack], **{group: dict(
            params[stack][group], **{leaf: params[stack][group][leaf] * 0})})})
        moved = jnp.abs(reference.forward(cut, rows[:, :-1], cfg) - base)
        assert float(moved.max()) > 1000 * TOL, (stack, keys)


@pytest.mark.parametrize("seed,held", [(0, (1, 4)), (1, (3, 4)), (2, (0, 2)),
                                       (3, None)])
def test_program_equals_reference_logits_loss_and_gradients(seed, held):
    cfg, params, rows = make(seed, experts_held=held)
    z_p = sm.forward(params, rows[:, :-1], cfg)
    z_r = reference.forward(params, rows[:, :-1], cfg)
    (l_p, metrics), g_p = sm.loss_metrics_and_grads(params, rows, cfg)
    l_r, g_r = sm.value_and_grad(reference_loss, cfg)(params, rows)
    assert float(z_r.std()) > 0.1
    assert float(jnp.abs(z_p - z_r).max()) < TOL
    # no router term: the program's whole loss IS its cross entropy
    assert float(l_p) == pytest.approx(float(l_r), abs=TOL)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_p),
                            jax.tree.leaves(g_r)):
        scale = float(jnp.abs(b).max())
        name = jax.tree_util.keystr(path)
        if name == "['layers']['router']['b']":
            assert scale == 0.0 and not bool(jnp.any(a)), name
            continue
        assert scale > 0, name
        assert float(jnp.abs(a - b).max()) < 20 * TOL * max(scale, 1.0), name
    assert float(metrics["moe_bias_swapped"]) > 0.05


def test_scanned_unrolled_and_rematted_layers_are_the_same_model():
    cfg, params, rows = make()

    def loss_and_grads(cfg):
        (loss, _), grads = sm.loss_metrics_and_grads(params, rows, cfg)
        return loss, grads

    want = loss_and_grads(cfg)
    for changes in (dict(scan_layers=False), dict(remat=False),
                    dict(scan_layers=False, remat=False),
                    dict(remat_policy="dots")):
        got = loss_and_grads(replace(cfg, **changes))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert float(jnp.abs(a - b).max()) < TOL, changes


@pytest.mark.parametrize("rotated", [True, False],
                         ids=["rope", "nope_as_kimi_linears_layer"])
def test_no_activation_is_cut_between_a_projection_and_the_kernels(rotated):
    """``_latent_qkv`` splits the WEIGHTS: nothing slices a ``[B, T, H, *]``
    activation but RoPE taking the halves of a projection's own output
    (rounded first), and each of the four by-head operands is a
    ``dot_general``'s output (the rotary query part through RoPE). On the
    chip a cut between a matmul and a custom call is a copy of the whole
    operand."""
    cfg, params, _ = make()
    own = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    rope = transformer.rope_frequencies(
        cfg.d_head_rope, cfg.max_seq_len, theta=cfg.rope_theta
    ) if rotated else None
    jaxpr = jax.make_jaxpr(
        lambda h, w: mixers._latent_qkv(h, w, cfg, rope, None))(
            jnp.zeros((2, T, cfg.d_model), jnp.float32), own).jaxpr
    made_by = {v: e for e in jaxpr.eqns for v in e.outvars}

    def by_head(v):
        shape = getattr(v.aval, "shape", ())
        return len(shape) == 4 and shape[:3] == (2, T, cfg.n_heads)

    def in_rope(e):
        return "attn_pos" in str(e.source_info.name_stack)

    cuts = [e for e in jaxpr.eqns if
            e.primitive.name in ("slice", "split", "dynamic_slice", "gather")
            and by_head(e.invars[0])]
    assert len(cuts) == rotated and all(in_rope(e) for e in cuts), cuts
    q_nope, k_nope, v, k_shared, q_shared = jaxpr.outvars   # keys sorted
    assert not by_head(k_shared) and by_head(q_shared)
    source, passed = made_by[q_shared], set()
    while in_rope(source):              # back through RoPE's arithmetic
        passed.add(source.primitive.name)
        source = made_by[next(v for v in source.invars if by_head(v))]
    # RoPE reads the projection rounded: XLA may not hand it the accumulator
    assert ("reduce_precision" in passed) == rotated == bool(passed)
    for e in (made_by[q_nope], made_by[k_nope], made_by[v], source):
        assert e.primitive.name == "dot_general", e


def _scan_unrolls(cfg):
    """The ``unroll`` of every ``scan`` in the model's forward."""
    tokens = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: models.forward(p, t, cfg))(
        cfg.shapes(), tokens)
    return [e.params["unroll"] for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "scan"]


def test_a_few_large_layers_run_in_line_and_everything_else_as_a_loop():
    """At most ``_SCAN_UNROLL_MOST`` steps over at least
    ``_SCAN_UNROLL_BYTES`` of parameters: unrolled in line (what lets
    kanana-2's cell fit its chip); a longer or a smaller stack: the loop,
    as ever; a stack of ONE step (OLMoE's cell, a period of
    SmallThinker's) is what it was."""
    assert (transformer._SCAN_UNROLL_MOST, transformer._SCAN_UNROLL_BYTES) \
        == (4, 2 ** 30)
    stack = lambda n, mb: {"w": jax.ShapeDtypeStruct(   # noqa: E731
        (n, mb, 2 ** 18), jnp.float32)}
    assert transformer._scan_unroll(stack(4, 256), 4) == 4
    assert transformer._scan_unroll(stack(2, 512), 2) == 2
    assert transformer._scan_unroll(stack(4, 255), 4) == 1      # small
    assert transformer._scan_unroll(stack(5, 256), 5) == 1      # long
    assert transformer._scan_unroll(stack(1, 2048), 1) == 1
    cell = spec.model_config(spec.load_json(
        "chipbench", "configs", "kanana-2-30b-a3b-ep8.json"))
    assert _scan_unrolls(cell) == [1, 4]                # dense, experts
    for name in ("gpt2-xl-1chip", "mistral-7b-fsdp4", "olmoe-1b-7b-1chip",
                 "smallthinker-21b-a3b-ep4"):
        other = spec.model_config(spec.load_json("chipbench", "configs",
                                                 name + ".json"))
        assert _scan_unrolls(other) == [1], name
    assert _scan_unrolls(small()) == [1, 1]
    assert _scan_unrolls(models.tiny(n_layers=4)) == [1]


FAULTS = {
    "the bias in the gates": None,          # planted by hand below
    "gate scale 1": dict(expert_gate_scale=1.0),
    "softmax scores": dict(router_score="softmax"),
    "gates not renormalised": dict(expert_norm_topk=False),
    "no shared expert": "shared",
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_a_fault_fails_the_comparison(name, monkeypatch):
    """Each of this model's own faults moves the program's logits from
    the reference's by far more than the sound program's rounding."""
    cfg, params, rows = make()
    fault = FAULTS[name]
    if fault is None:
        # a router whose gates are the BIASED scores of the chosen experts
        def biased_route(logits, top_k, norm_topk=True, *, score, select_bias,
                         gate_scale):
            scores = jax.nn.sigmoid(logits.astype(jnp.float32)) + select_bias
            topv, topi = jax.lax.top_k(scores, top_k)
            topv = gate_scale * topv / topv.sum(-1, keepdims=True)
            return scores, topv, topi

        monkeypatch.setattr(moe, "route", biased_route)
    elif fault == "shared":
        mlp = dict(params["layers"]["mlp"])
        mlp["shared_w_down"] = mlp["shared_w_down"] * 0
        params = dict(params, layers=dict(params["layers"], mlp=mlp))
        fault = {}
    # a patched program is in no key of ``sm``: it runs op by op
    run = models.forward if fault is None else sm.forward
    z_p = run(params, rows[:, :-1], replace(cfg, **(fault or {})))
    _, good, _ = make()
    z_r = reference.forward(good, rows[:, :-1], cfg)
    assert float(jnp.abs(z_p - z_r).max()) > 100 * TOL, name


# -- the share --------------------------------------------------------------------

def _block(x, lp, cfg, dense):
    rope = transformer.rope_frequencies(cfg.d_head_rope, cfg.max_seq_len,
                                        theta=cfg.rope_theta)
    return transformer._block(x, lp, cfg, rope=rope,
                              con=lambda t, *spec: t, dense=dense)[0]


def _one_layer(x, lp, cfg, dense=False):
    return sm.jitted(_block, cfg, dense)(x, lp)


def _reference_layer(x, lp, cfg, dense=False, first_held=0):
    return jax.jit(reference._layer, static_argnums=tuple(range(2, 9)))(
        x, lp, dense, cfg.d_head_nope, cfg.kv_latent, float(cfg.rope_theta),
        cfg.expert_top_k, float(cfg.expert_gate_scale), first_held)


@pytest.mark.parametrize("layer", [0, 1])
def test_the_ranks_routed_parts_and_the_shared_expert_once_sum_to_the_uncut_layer(
        layer):
    """One expert layer on the same input: each rank's program block gives
    ``h + its held experts' part + the shared expert``. What every rank
    computes alike (``h`` and the shared expert) counted ONCE, the four
    routed parts sum to the UNCUT reference's layer, which holds all 8
    experts."""
    cfg, full, rows = make(experts_held=None)
    x = full["embed"]["tokens"][rows[:, :-1]] * 10.0
    lp = _common.layer_slice(full["layers"], layer)
    alike = sm.ranks_parts_sum_to_the_uncut_layer(       # h + shared expert
        x, lp, cfg, RANKS, lambda x, lp: _reference_layer(x, lp, cfg),
        _one_layer, TOL)
    bare = dict(lp, mlp=dict(lp["mlp"], w_down=lp["mlp"]["w_down"] * 0,
                             shared_w_down=lp["mlp"]["shared_w_down"] * 0))
    h = _reference_layer(x, bare, cfg)
    assert float(jnp.abs(alike - h).max()) > 1000 * TOL     # the shared part


def test_the_dense_layer_is_the_references():
    cfg, params, rows = make()
    x = params["embed"]["tokens"][rows[:, :-1]] * 10.0
    lp = _common.layer_slice(params["dense_layers"], 0)
    with jax.default_matmul_precision("highest"):
        want = _reference_layer(x, lp, cfg, dense=True)
        got = _one_layer(x, lp, cfg, dense=True)
    assert float(jnp.abs(got - want).max()) < 5 * TOL
    assert float(jnp.abs(want - x).max()) > 1000 * TOL


# -- the router: the bias enters the choice and nothing else ----------------------

def _numpy_route(logits, bias, scale=2.448):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    chosen = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :K]
    picked = np.take_along_axis(s, chosen, -1)
    return s, chosen, scale * picked / picked.sum(-1, keepdims=True)


def test_the_bias_changes_the_choice_and_no_gate():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((96, E)).astype(np.float32)
    bias = (rng.standard_normal(E) * 0.5).astype(np.float32)
    s, chosen, gates = _numpy_route(logits, bias)
    _, plain_chosen, _ = _numpy_route(logits, np.zeros(E))
    probs, got_gates, got_chosen = moe.route(
        jnp.asarray(logits), K, True, score="sigmoid",
        select_bias=jnp.asarray(bias), gate_scale=2.448)
    assert np.array_equal(np.sort(np.asarray(got_chosen), -1),
                          np.sort(chosen, -1))
    assert not np.array_equal(np.sort(chosen, -1), np.sort(plain_chosen, -1))
    order = np.argsort(np.asarray(got_chosen), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(got_gates), order, -1),
        np.take_along_axis(gates, np.argsort(chosen, -1), -1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_gates).sum(-1), 2.448,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(probs), s / s.sum(-1, keepdims=True),
                               rtol=1e-5)
    # the counter: the share of the assignments the bias swapped in
    swapped = np.mean([[e not in plain_chosen[n] for e in chosen[n]]
                       for n in range(len(chosen))])
    assert swapped > 0.05
    assert float(moe.bias_swapped(jnp.asarray(logits), got_chosen, K)) == \
        pytest.approx(swapped, abs=1e-6)
    # no gradient reaches the bias, and the softmax router is what it was
    grad = jax.grad(lambda b: moe.route(
        jnp.asarray(logits), K, True, score="sigmoid", select_bias=b,
        gate_scale=2.448)[1].sum())(jnp.asarray(bias))
    assert not bool(jnp.any(grad))
    p, g, c = moe.route(jnp.asarray(logits), K, False)
    want = jax.nn.softmax(jnp.asarray(logits), -1)
    top = jax.lax.top_k(want, K)
    assert bool(jnp.array_equal(p, want)) and bool(jnp.array_equal(g, top[0]))
    assert bool(jnp.array_equal(c, top[1]))


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_the_steps_rule_moves_the_bias_and_adamws_decay_does_not(accum_steps):
    """After a step every layer's bias is what it WAS plus exactly
    ``rate x sign(mean load - the expert's load)`` from the step's own
    counts over all 8 experts; AdamW (decay 0.1, which moves every other
    leaf) and its moments never see it."""
    cfg, params, rows = make(router_bias_rate=0.01)
    rows = jnp.concatenate([rows, rows[::-1] + 1], 0) % cfg.vocab_size
    opt = sm.adamw(3e-4, weight_decay=0.1)
    state = sm.train_state(params, opt)
    new, metrics = sm.train_step(cfg, opt, accum_steps)(
        state, {"tokens": rows})
    assert "moe_expert_counts" not in metrics
    assert all(np.ndim(v) == 0 for v in metrics.values())
    counts = sm.lm_loss(params, rows, cfg)[1]["moe_expert_counts"]
    assert counts.shape == (2, E) and float(counts.sum()) == 2 * 4 * T * K
    old = params["layers"]["router"]["b"]
    want = old + 0.01 * jnp.sign(counts.mean(-1, keepdims=True) - counts)
    got = new["params"]["layers"]["router"]["b"]
    assert bool(jnp.array_equal(got, want))
    assert bool(jnp.any(got > old)) and bool(jnp.any(got < old))
    assert float(metrics["router_bias_absmax"]) == pytest.approx(
        float(jnp.abs(want).max()))
    mu = new["opt_state"][0].mu["layers"]["router"]["b"]
    assert not bool(jnp.any(mu))
    # every other leaf moved, by the optimizer
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(new["params"]),
            jax.tree.leaves(params)):
        assert bool(jnp.any(a != b)), jax.tree_util.keystr(path)
    # rate 0: the bias stays EXACTLY what it was (no decay either)
    still = sm.train_step(replace(cfg, router_bias_rate=0.0), opt)(
        state, {"tokens": rows})[0]
    assert bool(jnp.array_equal(still["params"]["layers"]["router"]["b"],
                                old))


def test_the_train_steps_counters_count_the_whole_batch():
    cfg, params, rows = make()
    metrics = sm.lm_loss(params, rows, cfg)[1]
    n = 2 * T
    share, load, swapped, over = [], [], [], []
    x = params["embed"]["tokens"][rows[:, :-1]]
    rope = transformer.rope_frequencies(cfg.d_head_rope, cfg.max_seq_len,
                                        theta=cfg.rope_theta)
    x = transformer._block(
        x, _common.layer_slice(params["dense_layers"], 0), cfg, rope=rope,
        con=lambda t, *spec: t, dense=True)[0]
    for i in range(cfg.n_scan_layers):
        lp = _common.layer_slice(params["layers"], i)
        a = x + reference._attention(
            reference._rms(x, lp["ln1"]["w"]), lp["attn"], cfg.d_head_nope,
            cfg.kv_latent, float(cfg.rope_theta))
        u = np.asarray(reference._rms(a, lp["ln2"]["w"])).reshape(n, -1)
        logits = u @ np.asarray(lp["router"]["w"])
        _, chosen, _ = _numpy_route(logits, np.asarray(lp["router"]["b"]))
        _, plain, _ = _numpy_route(logits, np.zeros(E))
        counts = np.bincount(chosen.reshape(-1), minlength=E)
        first, end = moe.held_range(E, *cfg.experts_held)
        share.append(counts[first:end].sum() / (n * K))
        load.append(counts[first:end].max() / counts[first:end].mean())
        over.append(counts[first:end].sum() > moe._buffer_rows(
            n * K, end - first, E))
        swapped.append(np.mean([[e not in plain[t] for e in chosen[t]]
                                for t in range(n)]))
        np.testing.assert_array_equal(
            np.asarray(metrics["moe_expert_counts"][i]), counts)
        x = transformer._block(x, lp, cfg, rope=rope,
                               con=lambda t, *spec: t)[0]
    assert float(metrics["moe_held_share"]) == pytest.approx(
        np.mean(share), abs=1e-6)
    assert float(metrics["moe_load_max"]) == pytest.approx(max(load),
                                                           rel=1e-5)
    assert float(metrics["moe_bias_swapped"]) == pytest.approx(
        np.mean(swapped), abs=1e-6)
    # half of the experts are held: the row buffer is every assignment
    assert float(metrics["moe_full_buffer"]) == np.mean(over) == 0.0


# -- what is refused, by name -----------------------------------------------------

@pytest.mark.parametrize("changes,named", [
    (dict(qk_norm=True), "latent attention .* qk_norm"),
    (dict(n_kv_heads=2), "latent attention .* GQA"),
    (dict(layer_pattern=((False, True),)), "latent attention .* layer_pattern"),
    (dict(arch="gpt2", n_experts=0, n_dense_layers=0, d_ff_shared=0,
          router_score="softmax", router_bias=False, router_bias_rate=0.0,
          expert_gate_scale=1.0, experts_held=None),
     "latent attention .* arch"),
    (dict(d_head_rope=7), "even d_head_rope"),
    (dict(d_head_v=0), "latent attention needs"),
    (dict(n_dense_layers=3), "n_dense_layers are the first"),
    (dict(d_ff_dense=None), "d_ff_dense"),
    (dict(router_score="tanh"), "router_score must be one of"),
    (dict(router_bias=False), "router_bias_rate moves the router_bias"),
    (dict(expert_capacity_factor=1.25), "the dropless path's"),
])
def test_what_the_config_refuses(changes, named):
    with pytest.raises(ValueError, match=named):
        models.init_params(jax.random.PRNGKey(0), small(**changes))


def test_a_leading_dense_stack_needs_experts_behind_it():
    with pytest.raises(ValueError, match="n_dense_layers are the first"):
        models.init_params(jax.random.PRNGKey(0), models.tiny(
            arch="llama", n_dense_layers=1, d_ff_dense=64))


def test_no_serving_path_runs_this_model():
    """The KV cache holds ``kv_heads x head_dim x 2`` a token and decodes
    dense layers of plain attention: latent attention, a leading dense
    stack and a shared expert are refused by name, ahead of the general
    refusal of experts."""
    cfg, params, rows = make()
    with pytest.raises(NotImplementedError, match="kv_latent"):
        models.init_kv_cache(cfg, 1, 32)
    with pytest.raises(NotImplementedError, match="kv_latent"):
        models.decode_step(None, jnp.zeros((1, 1), jnp.int32),
                           {"pos": jnp.zeros((), jnp.int32)}, cfg)
    plain = dict(kv_latent=None, d_head_nope=0, d_head_rope=0, d_head_v=0)
    for field, changes in (
            ("n_dense_layers", plain),
            ("d_ff_shared", dict(plain, n_dense_layers=0, d_ff_dense=None))):
        with pytest.raises(NotImplementedError, match=field):
            transformer.refuse_decode(replace(cfg, **changes))
    with pytest.raises(NotImplementedError, match="MoE"):
        transformer.refuse_decode(replace(
            cfg, **plain, n_dense_layers=0, d_ff_dense=None, d_ff_shared=0))
    dense_mla = models.tiny(arch="llama", kv_latent=32, d_head_nope=16,
                            d_head_rope=8, d_head_v=16)
    with pytest.raises(NotImplementedError, match="kv_latent"):
        models.init_kv_cache(dense_mla, 1, 32)


# -- partitioning -----------------------------------------------------------------

def test_the_second_stack_goes_through_partition_specs_on_a_virtual_mesh():
    """Both stacks get the megatron layout (heads and FFN width over
    ``tensor``), fsdp on top; a step on a (data 2, fsdp 2, tensor 2) mesh
    of the CPU's virtual devices is the unsharded step."""
    from jax.sharding import PartitionSpec as P

    cfg, params, rows = make(experts_held=None)
    specs, placed = sm.sharded_loss_is_the_unsharded(cfg, params, rows, TOL)
    for stack in ("layers", "dense_layers"):
        attn = specs[stack]["attn"]
        assert attn["wq"] == attn["wkv_b"] == P(None, None, "tensor", None)
        assert attn["wo"] == P(None, "tensor", None, None)
        assert attn["wkv_a"] is None and attn["kv_norm"] is None
    assert specs["dense_layers"]["mlp"]["w_down"] == P(None, "tensor", None)
    assert specs["layers"]["mlp"]["shared_w_gate"] == P(None, None, "tensor")
    assert specs["layers"]["mlp"]["w_gate"] == P(None, "expert", None,
                                                 "tensor")
    assert specs["layers"]["router"]["b"] is None
    assert len(placed["dense_layers"]["mlp"]["w_gate"].sharding.device_set) == 8


# -- scopes -----------------------------------------------------------------------

def test_the_new_scopes_are_on_the_instructions():
    """``attn_full`` and, inside it, ``mla_latent`` in both stacks; the
    shared expert under ``moe`` / ``moe_shared``; the dense layer's FFN
    under ``mlp``; the bias's rule under ``optimizer``."""
    cfg, params, rows = make()
    opt = sm.adamw(3e-4)
    text = sm.train_step(cfg, opt).lower(
        sm.train_state(params, opt), {"tokens": rows}).as_text(
            debug_info=True)
    for path in ("attn/attn_full/mla_latent", "moe/moe_shared",
                 "moe/moe_router", "moe/moe_experts", "/mlp/",
                 "optimizer/sign"):
        assert path in text, path
    assert "attn_window" not in text
    assert "moe_shared" in moe.SCOPES and transformer.MLA_SCOPE == "mla_latent"
    assert transformer.SCOPE_FILES[:2] == (transformer.__file__, moe.__file__)
