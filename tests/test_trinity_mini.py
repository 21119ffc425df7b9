"""Trinity-Mini (an ``afmoe``-shaped model) on the normal path against its
plain reference (``chipbench/reference/afmoe.py``), at a Trinity-shaped
small size on the CPU: published layers 1 to 5 (one dense layer, SwiGLU
96, then S F S S), hidden 64, 4 query heads on 2 key / value heads of 16,
window 16 at rows of 64, each head's q and k normed, attention's output
gated, a norm on each sublayer's output, the embedding times 8, 8 SwiGLU
experts of width 32, 3 a token by a sigmoid router whose bias only the
choice sees, gates renormalised and scaled by 2.826, a shared expert 48
wide, no router loss. The parameters hold rank 1 of 4's experts (2 of the
8) unless a test says otherwise.

Weights: as in ``tests/test_kanana2.py``, the layer weights are drawn at
``SCALE`` x the program's N(0, 0.02), the router at 10 x that again and
the bias at 5 x, the norms' weights (all four of a block, the two head
norms) spread around 1, so that every branch moves the logits, routing is
uneven, the bias changes the choice of many tokens and no norm weight is
a silent 1. Both sides compute in float32: the tolerances are float32
rounding grown by the depth of the sums; a fault has to miss by 100 x
that.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from chipbench.reference import _common
from chipbench.reference import afmoe as reference
from ray_tpu import models
from ray_tpu.models import mixers, transformer
from ray_tpu.ops import moe

import _small_models as sm

TOL = 2e-5
T, E, K, RANKS, WINDOW = 64, 8, 3, 4, 16
S, F = (True, True), (False, False)
NORMS = ("ln1", "ln2", "ln1_post", "ln2_post")


def small(**kw):
    base = dict(
        n_layers=5, first_layer=1, n_dense_layers=1, d_model=64, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=32, d_ff_dense=96, d_ff_shared=48,
        n_experts=E, expert_top_k=K, vocab_size=256, max_seq_len=T,
        sliding_window=WINDOW, embed_scale=8.0, experts_held=(1, RANKS),
        dtype="float32")
    base.update(kw)
    return models.trinity_mini_26b_a3b(**base)


def make(seed: int = 0, **kw):
    """(cfg, params, rows [2, T + 1])."""
    cfg, params, rows = sm.make(
        small, seed, tokens=T, bias_scale=5.0,
        as_drawn=NORMS + ("q_norm", "k_norm"), **kw)
    spread = iter(jax.random.split(jax.random.PRNGKey(seed + 500), 64))

    def around_one(a):
        return a + 0.3 * jax.random.normal(next(spread), a.shape, a.dtype)

    for stack in ("layers", "dense_layers"):
        layers = params[stack]
        for name in NORMS:
            layers[name]["w"] = around_one(layers[name]["w"])
        for name in ("q_norm", "k_norm"):
            layers["attn"][name] = around_one(layers["attn"][name])
    return cfg, params, rows


def reference_loss(params, rows, cfg):
    return _common.next_token_loss(
        reference.forward(params, rows[:, :-1], cfg), rows)


# -- the preset ---------------------------------------------------------------

def test_preset_is_trinity_mini_as_published():
    c = models.trinity_mini_26b_a3b()
    data = spec.load_json("chipbench", "configs",
                          "trinity-mini-26b-a3b-ep16.json")
    published = {**data, **data["published"]}
    want = tuple(published[k] for k in (
        "num_hidden_layers", "num_dense_layers", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "moe_intermediate_size", "num_experts",
        "num_experts_per_tok", "sliding_window", "vocab_size",
        "max_position_embeddings"))
    assert want[-5:] == (128, 8, 2048, 200192, 131072)
    assert (c.n_layers, c.n_dense_layers, c.d_model, c.n_heads, c.kv_heads,
            c.head_dim, c.d_ff_dense, c.ffn_dim, c.n_experts, c.expert_top_k,
            c.sliding_window, c.vocab_size, c.max_seq_len) == want
    assert c.d_ff_shared == published["num_shared_experts"] * 1024 == 1024
    assert (c.router_score, c.router_bias, c.router_bias_rate,
            c.expert_gate_scale, c.expert_norm_topk, c.router_aux_weight,
            c.router_z_weight, c.expert_capacity_factor, c.rope_theta,
            c.norm_eps, c.tied, c.arch) == (
        "sigmoid", True, published["load_balance_coeff"],
        published["route_scale"], True, 0.0, 0.0, None, 1e4, 1e-5, False,
        "llama")
    assert (c.qk_norm, c.attn_gate, c.post_norm, c.first_layer) == (
        "head", True, True, 0)
    assert c.embed_scale == 2048 ** 0.5
    kinds = [c.layer_kind(i) for i in range(c.n_layers)]
    assert kinds == [S if t == "sliding_attention" else F
                     for t in published["layer_types"]]
    assert c.n_scan_layers == 30 and c.experts_held is None
    assert c.num_params() == pytest.approx(26.1e9, rel=5e-3)


# -- the pattern behind a dense stack ---------------------------------------------

def test_the_published_layers_1_to_5_have_their_kinds():
    cfg = small()
    assert [cfg.layer_kind(i) for i in range(5)] == [S, S, F, S, S]
    # the dense stack's layer is a windowed one; the expert stack's period
    # starts mid-pattern
    assert cfg.n_dense_layers == 1 and cfg.n_scan_layers == 4
    # from layer 0 with both dense layers: S S | S F S S S F
    whole = small(first_layer=0, n_layers=8, n_dense_layers=2)
    assert [whole.layer_kind(i) for i in range(8)] == [S, S, S, F, S, S, S, F]
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    assert params["dense_layers"]["attn"]["wq"].shape == (1, 64, 4, 16)
    assert params["layers"]["attn"]["wg"].shape == (4, 64, 4, 16)
    assert params["layers"]["attn"]["q_norm"].shape == (4, 16)
    assert params["dense_layers"]["attn"]["k_norm"].shape == (1, 16)
    for stack, n in (("layers", 4), ("dense_layers", 1)):
        for name in NORMS:
            assert params[stack][name]["w"].shape == (n, 64)
    assert params["layers"]["mlp"]["w_gate"].shape == (4, 2, 64, 32)
    assert params["layers"]["router"]["w"].shape == (4, 64, 8)
    assert float(jnp.abs(params["layers"]["router"]["b"]).min()) > 0


@pytest.mark.parametrize("kw", [
    dict(), dict(n_layers=12, first_layer=0, n_dense_layers=2, remat=False)],
    ids=["cell", "periods+left"])
def test_the_scan_over_periods_equals_the_unrolled_model(kw):
    """One dense layer and the expert stack S F S S (one period, no whole
    repeat); two dense layers scanned and S F S S twice with S F behind
    them (a real scan over periods with layers left): the scanned model's
    logits are the unrolled one's (its gradients are held to the
    reference's below, leaf by leaf)."""
    cfg, params, rows = make(**kw)
    z = sm.forward(params, rows[:, :-1], cfg)
    z_loop = sm.forward(params, rows[:, :-1], replace(cfg, scan_layers=False))
    assert float(jnp.abs(z - z_loop).max()) < TOL


# -- the program against the reference ----------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(), dict(experts_held=None),
    dict(n_layers=8, first_layer=0, n_dense_layers=2)],
    ids=["share", "uncut", "from0"])
def test_logits_and_loss_are_the_references(kw):
    cfg, params, rows = make(**kw)
    z_p = sm.forward(params, rows[:, :-1], cfg)
    z_r = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.std(z_r)) > 0.1
    assert float(jnp.abs(z_p - z_r).max()) < 5 * TOL
    assert float(sm.loss(params, rows, cfg)) == pytest.approx(
        float(reference_loss(params, rows, cfg)), abs=TOL)


def test_every_leafs_gradient_is_the_references():
    """``jax.grad`` of the reference's loss on the same seeded weights:
    the gate, both head norms, both output norms and the embedding
    (through its scale) among the leaves; the router's bias alone has no
    gradient, on either side."""
    cfg, params, rows = make()
    g_p = sm.loss_metrics_and_grads(params, rows, cfg)[1]
    g_r = sm.grad(reference_loss, cfg)(params, rows)
    flat_p = dict(jax.tree_util.tree_flatten_with_path(g_p)[0])
    flat_r = dict(jax.tree_util.tree_flatten_with_path(g_r)[0])
    assert flat_p.keys() == flat_r.keys()
    seen = set()
    for path, a in flat_p.items():
        name = "/".join(k.key for k in path)
        b = flat_r[path]
        if name == "layers/router/b":
            assert float(jnp.abs(a).max()) == float(jnp.abs(b).max()) == 0.0
            continue
        size = float(jnp.abs(b).max())
        assert size > 0, name
        assert float(jnp.abs(a - b).max()) < 2e-4 * size + 1e-7, name
        seen.add(name)
    for stack in ("layers", "dense_layers"):
        for leaf in ("attn/wg", "attn/q_norm", "attn/k_norm", "ln1_post/w",
                     "ln2_post/w"):
            assert f"{stack}/{leaf}" in seen
    assert "embed/tokens" in seen


FAULTS = {
    "the gate off": dict(attn_gate=False),
    "the head norms off": dict(qk_norm=False),
    "the head norms as one norm over all heads": "whole",
    "the output norms off": dict(post_norm=False),
    "the window one key short": dict(sliding_window=WINDOW - 1),
    "RoPE on the global layers": dict(layer_pattern=(S, S, S, (False, True))),
    "the global layer windowed": dict(layer_pattern=(S, S, S, S)),
    "the pattern anchored at layer 0": dict(first_layer=0),
    "the embedding not scaled": dict(embed_scale=1.0),
    "the gates not scaled": dict(expert_gate_scale=1.0),
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_the_comparison_sees(name):
    """The program under another configuration than the reference's, on
    the reference's weights (``compare --break``): each new mechanism is
    a field whose flip moves the logits by far more than rounding."""
    cfg, params, rows = make()
    fault = FAULTS[name]
    if fault == "whole":
        # OLMoE's norm over all the heads together, with the head's weight
        # repeated to every head: the same weights, another mean
        fault = dict(qk_norm=True)
        for stack in ("layers", "dense_layers"):
            attn = dict(params[stack]["attn"])
            attn["q_norm"] = jnp.tile(attn["q_norm"], (1, cfg.n_heads))
            attn["k_norm"] = jnp.tile(attn["k_norm"], (1, cfg.kv_heads))
            params = dict(params, **{stack: dict(params[stack], attn=attn)})
    z_p = sm.forward(params, rows[:, :-1], replace(cfg, **fault))
    _, good, _ = make()
    z_r = reference.forward(good, rows[:, :-1], cfg)
    assert float(jnp.abs(z_p - z_r).max()) > 100 * TOL, name


# -- the share --------------------------------------------------------------------

def _reference_ffn(m, lp, cfg, first_held=0):
    return reference._ffn(m, lp, False, cfg.expert_top_k,
                          float(cfg.expert_gate_scale), first_held)


@pytest.mark.parametrize("layer", [0, 1])
def test_the_sixteenths_and_the_shared_expert_once_sum_to_the_uncut_layer(
        layer):
    """One expert layer on the same input. Ahead of the FFN's output norm
    each rank's program gives ``its held experts' part + the shared
    expert``; what every rank computes alike (the shared expert) counted
    ONCE, the routed parts of all the ranks sum to the UNCUT reference's
    FFN output, which holds all 8 experts; normed and added to the stream
    that is the uncut reference's layer, and the program that holds every
    expert is that layer too."""
    cfg, full, rows = make(experts_held=None)
    x = full["embed"]["tokens"][rows[:, :-1]] * 8.0
    lp = _common.layer_slice(full["layers"], layer)
    kind = cfg.layer_kind(cfg.n_dense_layers + layer)
    window = cfg.sliding_window if kind[0] else None
    rope = transformer.rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                        theta=cfg.rope_theta)
    with jax.default_matmul_precision("highest"):
        uncut = jax.jit(reference._layer, static_argnums=tuple(range(2, 8)))(
            x, lp, False, window, float(cfg.rope_theta), cfg.expert_top_k,
            float(cfg.expert_gate_scale), 0)
        after_attn = reference._mixer(x, lp, window, float(cfg.rope_theta))
        m = reference._rms(after_attn, lp["ln2"]["w"])
        flat = m.reshape(-1, m.shape[-1])
        whole = _reference_ffn(flat, lp, cfg)
        no_routed = dict(lp, mlp=dict(lp["mlp"],
                                      w_down=lp["mlp"]["w_down"] * 0))
        alike = _reference_ffn(flat, no_routed, cfg)    # the shared expert
        assert float(jnp.abs(alike).max()) > 1000 * TOL
        parts = []
        for rank in range(RANKS):
            first, end = moe.held_range(E, rank, RANKS)
            mlp = {name: (w[first:end] if name.startswith("w_") else w)
                   for name, w in lp["mlp"].items()}
            f_rank, _ = transformer._expert_ffn(
                m, dict(lp, mlp=mlp),
                replace(cfg, experts_held=(rank, RANKS)), None,
                lambda t, *spec: t)
            parts.append(f_rank.reshape(flat.shape) - alike)
            # and the reference given the same share is that rank
            same = _reference_ffn(flat, dict(lp, mlp=mlp), cfg, first)
            assert float(jnp.abs(f_rank.reshape(flat.shape) - same).max()
                         ) < 5 * TOL
        assert all(float(jnp.abs(p).max()) > 1000 * TOL for p in parts)
        total = alike + sum(parts)
        assert float(jnp.abs(total - whole).max()) < 5 * TOL
        layer_out = after_attn + reference._rms(total.reshape(m.shape),
                                                lp["ln2_post"]["w"])
        assert float(jnp.abs(layer_out - uncut).max()) < 5 * TOL
        got = transformer._block(x, lp, cfg, rope=rope,
                                 con=lambda t, *spec: t, kind=kind)[0]
    assert float(jnp.abs(got - uncut).max()) < 5 * TOL


# -- what is refused, by name -----------------------------------------------------

@pytest.mark.parametrize("changes,named", [
    (dict(first_layer=None, n_layers=4), "n_dense_layers are the first"),
    (dict(first_layer=None, n_dense_layers=0, d_ff_dense=None),
     "whole number of periods"),
    (dict(first_layer=-1), "first_layer is the published number"),
    (dict(layer_pattern=(), sliding_window=None), "first_layer is the "
                                                  "published number"),
    (dict(qk_norm="heads"), "qk_norm must be"),
    (dict(sliding_window=None), "sliding_window is the width"),
    (dict(n_dense_layers=5), "n_dense_layers are the first"),
    (dict(d_ff_dense=None), "d_ff_dense"),
    (dict(router_bias=False), "router_bias_rate moves the router_bias"),
    (dict(expert_capacity_factor=1.25), "the dropless path's"),
    (dict(kv_latent=32, d_head_nope=16, d_head_rope=8, d_head_v=16,
          n_kv_heads=4, qk_norm=False, layer_pattern=(), first_layer=None,
          sliding_window=None), "attn_gate gates plain attention"),
    (dict(arch="gpt2", n_experts=0, n_dense_layers=0, d_ff_shared=0,
          router_score="softmax", router_bias=False, router_bias_rate=0.0,
          expert_gate_scale=1.0, experts_held=None, qk_norm=False,
          layer_pattern=(), first_layer=None, sliding_window=None,
          d_head=None, n_kv_heads=4), "attn_gate requires arch='llama'"),
])
def test_what_the_config_refuses(changes, named):
    with pytest.raises(ValueError, match=named):
        transformer._check_config(small(**changes))


def test_what_the_config_accepts():
    """The pattern with a dense stack, mid-period starts and stops, and
    each new field by itself on a plain llama."""
    for kw in (dict(), dict(n_layers=4), dict(n_layers=7, first_layer=2),
               dict(first_layer=0, n_layers=8, n_dense_layers=2)):
        transformer._check_config(small(**kw))
    for kw in (dict(attn_gate=True), dict(post_norm=True),
               dict(qk_norm="head"), dict(qk_norm=True),
               dict(embed_scale=8.0)):
        cfg = models.tiny(arch="llama", **kw)
        params = models.init_params(jax.random.PRNGKey(0), cfg)
        rows = jnp.zeros((1, 9), jnp.int32)
        assert np.isfinite(float(sm.loss(params, rows, cfg)))
    # a plain model's leaves are what they were
    plain = models.init_params(jax.random.PRNGKey(0), models.tiny(arch="llama"))
    assert set(plain["layers"]) == {"attn", "ln1", "ln2", "mlp"}
    assert set(plain["layers"]["attn"]) == {"wq", "wk", "wv", "wo"}


def test_no_serving_path_runs_this_model():
    """The KV-cache decode norms no head, gates and norms no output and
    scales no embedding: each new field is refused by name, ahead of the
    general refusal of experts."""
    cfg, params, rows = make()
    with pytest.raises(NotImplementedError, match="n_dense_layers"):
        models.init_kv_cache(cfg, 1, 32)
    dense = models.tiny(arch="llama")
    for field, value in (("qk_norm", "head"), ("qk_norm", True),
                         ("attn_gate", True), ("post_norm", True),
                         ("embed_scale", 8.0)):
        bad = replace(dense, **{field: value})
        with pytest.raises(NotImplementedError, match=field):
            transformer.refuse_decode(bad)
        with pytest.raises(NotImplementedError, match=field):
            models.decode_step(None, jnp.zeros((1, 1), jnp.int32),
                               {"pos": jnp.zeros((), jnp.int32)}, bad)
    with pytest.raises(NotImplementedError, match="not normed.*gated"):
        transformer.refuse_decode(replace(dense, attn_gate=True))
    anchored = replace(dense, layer_pattern=((False, True),), first_layer=0)
    with pytest.raises(NotImplementedError, match="layer_pattern|first_layer"):
        transformer.refuse_decode(anchored)
    transformer.refuse_decode(dense)


def test_partition_specs_cover_the_new_leaves():
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import AXIS_TENSOR

    cfg = small()
    specs = models.partition_specs(cfg)
    shapes = cfg.shapes()
    assert jax.tree.structure(
        specs, is_leaf=lambda s: s is None or isinstance(s, P)
    ).num_leaves == jax.tree.structure(shapes).num_leaves
    for stack in ("layers", "dense_layers"):
        assert specs[stack]["attn"]["wg"] == P(None, None, AXIS_TENSOR, None)
        assert specs[stack]["attn"]["q_norm"] is None
        assert specs[stack]["ln1_post"]["w"] is None
        assert specs[stack]["ln2_post"]["w"] is None


# -- the train step ---------------------------------------------------------------

def test_the_step_reports_the_gates_mean_and_moves_the_bias_by_rule():
    cfg, _, rows = make()
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    opt = sm.adamw(1e-3, weight_decay=0.1)
    new, metrics = sm.train_step(cfg, opt)(sm.train_state(params, opt),
                                           {"tokens": rows})
    # N(0, 0.02) gate weights on a unit-size input: logits of std 0.16
    assert float(metrics["attn_gate_mean"]) == pytest.approx(0.5, abs=0.01)
    assert np.ndim(metrics["attn_gate_mean"]) == 0
    moved = new["params"]["layers"]["router"]["b"] - params["layers"]["router"]["b"]
    assert set(np.unique(np.abs(np.asarray(moved)).round(7))) <= {
        0.0, np.float32(cfg.router_bias_rate).round(7)}
    assert float(jnp.abs(moved).max()) > 0
    for leaf in ("wg", "q_norm", "k_norm"):
        for stack in ("layers", "dense_layers"):
            assert float(jnp.abs(new["params"][stack]["attn"][leaf]
                                 - params[stack]["attn"][leaf]).max()) > 0
    # a gate that has shut reads 0
    o, mean = mixers._gate_output(
        jnp.ones((1, 8, 4, 16)), jnp.ones((1, 8, 64)),
        jnp.full((64, 4, 16), -10.0))
    assert float(mean) < 1e-6 and float(jnp.abs(o).max()) < 1e-6


def test_accumulation_keeps_the_counter():
    cfg, _, rows = make()
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    opt = sm.adamw(1e-3)
    _, metrics = sm.train_step(cfg, opt, accum_steps=2)(
        sm.train_state(params, opt), {"tokens": rows})
    assert float(metrics["attn_gate_mean"]) == pytest.approx(0.5, abs=0.01)
