"""The ``phi4flash`` arch (Phi-4-mini-flash-reasoning, SambaY) at test size
on the CPU, float32, seeded weights: the program (``ray_tpu.models``:
Mamba-1 selective-scan layers, differential attention windowed and full, a
memory the stack carries from ONE layer to the gated memory units and cross
layers behind it, LayerNorm with a bias, a tied head) against the plain
reference (``chipbench/reference/phi4flash.py``: the recurrence token by
token, the two softmax maps materialised) for logits, loss and the gradient
of every leaf at a depth with all six kinds of layer and TWO reader pairs
(so the summed gradient of ``m``, ``k``, ``v`` is held); differential
attention against two materialised maps; what a wrong memory reads; what
``_check_config`` and ``refuse_decode`` refuse by name and index; the
scopes; the published depth's parameter count. One small model a file
(``tests/_small_models.py``): a case costs its distinct compiles."""

from __future__ import annotations

import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

import _small_models as sm
from _small_models import highest_precision  # noqa: F401  (autouse)
from chipbench.reference import _common
from chipbench.reference import phi4flash as reference
from ray_tpu import models
from ray_tpu.models import mixers, transformer

T = 24
TOL = 2e-5
# published layers 14-21: ssm1, window, ssm1 (hands out m), full (hands out
# k, v), then TWO [gmu, cross] pairs
KINDS = ("ssm1", "attn", "ssm1", "attn", "gmu", "cross", "gmu", "cross")
AS_DRAWN = ("ln1", "ln2", "A_log", "dt_bias", "D", "conv_w", "conv_b",
            "sub_norm", "lambdas")


def small(**kw):
    base = dict(
        n_layers=8, first_layer=14, vocab_size=128, d_model=32, n_heads=4,
        n_kv_heads=2, d_head=8, d_ff=48, ssm_state=4, ssm_dt_rank=4,
        ssm_chunk=8, sliding_window=5, max_seq_len=T, dtype="float32")
    base.update(kw)
    return models.phi4_mini_flash_reasoning(**base)


def make(seed: int = 0, **kw):
    """(cfg, params, rows [2, T + 1]): every matrix and projection bias at 5
    x its draw, the norms' biases drawn N(0, 0.3) (the init makes them 0),
    the scan's own small leaves and the ``lambda`` vectors as drawn."""
    cfg = small(**kw)
    params = models.init_params(jax.random.PRNGKey(seed), cfg)

    def one(path, a):
        names = [k.key for k in path]
        if names[-1] == "b" and names[0] in ("ln1", "ln2"):
            return 0.3 * jax.random.normal(
                jax.random.PRNGKey(seed + len(names[0]) + a.shape[0]),
                a.shape)
        return a if set(names) & set(AS_DRAWN) else a * sm.SCALE

    params = dict(params, layers=jax.tree_util.tree_map_with_path(
        one, params["layers"]))
    rows = jax.random.randint(jax.random.PRNGKey(seed + 1000), (2, T + 1), 0,
                              cfg.vocab_size)
    return cfg, params, rows


def _reference_loss(params, rows, cfg):
    return _common.next_token_loss(reference.forward(params, rows[:, :-1],
                                                     cfg), rows)


# -- program against reference ---------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_logits_loss_and_every_leafs_gradient_are_the_references(seed):
    cfg, params, rows = make(seed)
    assert cfg.layer_mixers == KINDS
    z_p = sm.forward(params, rows[:, :-1], cfg)
    z_r = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(z_r).max()) > 0.3
    assert float(jnp.abs(z_p - z_r).max()) < TOL
    (loss, metrics), grads = sm.loss_metrics_and_grads(params, rows, cfg)
    want, want_grads = jax.value_and_grad(_reference_loss)(params, rows, cfg)
    assert float(loss) == pytest.approx(float(want), abs=TOL)
    assert 0.5 < float(metrics["attn_diff_lambda"]) < 1.0
    assert 0 < float(metrics["ssm_step_mean"]) < 0.2
    assert float(metrics["kda_log_decay_min"]) < 0
    assert float(metrics["gmu_gate_mean"]) != 0

    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    theirs = jax.tree.leaves(want_grads)
    assert len(flat) == len(theirs) == len(jax.tree.leaves(params))
    for (path, mine), ref in zip(flat, theirs):
        name = "/".join(k.key for k in path)
        if name == "layers/mha/bk":     # a key's bias moves no softmax
            assert float(jnp.abs(mine).max()) < 1e-8 > float(
                jnp.abs(ref).max())
            continue
        size = float(jnp.abs(ref).max())
        assert size > 0, name
        assert float(jnp.abs(mine - ref).max()) < 1e-4 * size + 1e-7, name


def test_the_stacks_hold_each_kinds_leaves_and_the_memory_has_one_writer():
    cfg, params, rows = make()
    n = {name: jax.tree.leaves(sub)[0].shape[0]
         for name, sub in params["layers"].items()}
    # ``attn/wo`` over the layers as wide inside as attention, no others
    assert n == {"attn": 4, "cross": 2, "gmu": 2, "ln1": 8, "ln2": 8,
                 "mha": 2, "mlp": 8, "ssm1": 2}
    assert set(params["layers"]["attn"]) == {"wo", "bo"}
    assert set(params["layers"]["cross"]) == {
        "wq", "bq", "lambdas", "sub_norm"}          # no key, no value
    assert params["layers"]["cross"]["lambdas"].shape == (2, 4, 8)
    assert params["layers"]["ssm1"]["wo"].shape == (2, 64, 32)
    assert params["layers"]["ssm1"]["A_log"].shape == (2, 64, 4)
    assert set(params["final_norm"]) == {"w", "b"} and "lm_head" not in params
    assert transformer._writes(cfg) == ((), (), ("m",), ("kv",), (), (), (),
                                        ())
    assert [cfg.layer_kind(i) for i in range(4)] == [
        "ssm1", (True, False), "ssm1", (False, False)]
    assert cfg.linear_mixer == "ssm1" and not cfg.single_sublayer
    # the published depth: layer 16 and layer 17 write, nothing repeats
    whole = models.phi4_mini_flash_reasoning()
    writes = transformer._writes(whole)
    assert [i for i, w in enumerate(writes) if w] == [16, 17]
    assert whole.layer_mixers[:18] == ("ssm1", "attn") * 9
    assert whole.layer_mixers[18:] == ("gmu", "cross") * 7
    assert [whole.layer_kind(i)[0] for i in range(1, 18, 2)] == [True] * 8 + [
        False]
    # unrolled by hand, the layers are the stack's
    loose = replace(cfg, scan_layers=False, remat=False)
    assert float(jnp.abs(sm.forward(params, rows[:, :-1], cfg) - sm.forward(
        params, rows[:, :-1], loose)).max()) < TOL


def test_the_published_depth_counts_3_85_billion_parameters():
    d, inner, f, v = 2560, 5120, 10240, 200064
    mamba = (d * 2 * inner + inner * d + inner * (160 + 32) + 160 * inner
             + inner + inner * 16 + inner * 4 + inner + inner)
    diff = 4 * 64 + 128
    attn = d * (2560 + 2 * 1280) + (2560 + 2 * 1280) + d * d + d + diff
    gmu = 2 * d * inner
    cross = 2 * (d * d + d) + diff
    mlp, norms = 3 * d * f, 2 * 2 * d
    assert (mamba, attn, gmu, cross) == (41_241_600, 19_668_864, 26_214_400,
                                         13_112_704)
    total = (9 * mamba + 9 * attn + 7 * gmu + 7 * cross + 32 * (mlp + norms)
             + v * d + 2 * d)
    assert models.phi4_mini_flash_reasoning().num_params() == total \
        == 3_852_562_944
    cut = models.phi4_mini_flash_reasoning(n_layers=6, first_layer=14,
                                           vocab_size=25088)
    assert cut.num_params() == 697_299_072
    assert cut.layer_mixers == KINDS[:6]


# -- differential attention -------------------------------------------------------

def _maps(q, k, v, window):
    """softmax(q k^T / sqrt(d), causal[, window]) v, materialised."""
    t = q.shape[1]
    d = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = (d >= 0) if window is None else (d >= 0) & (d < window)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _diff_operands(seed=3):
    cfg = small()
    k = jax.random.split(jax.random.PRNGKey(seed), 10)
    q1, q2 = (jax.random.normal(k[i], (2, T, 2, 8)) for i in (0, 1))
    k1, k2 = (jax.random.normal(k[i], (2, T, 1, 8)) for i in (2, 3))
    v = jax.random.normal(k[4], (2, T, 1, 16))
    w = {"lambdas": 0.3 * jax.random.normal(k[5], (4, 8)),
         "sub_norm": 1.0 + 0.1 * jax.random.normal(k[9], (16,))}
    return cfg, (q1, q2, k1, k2, v), w


@pytest.mark.parametrize("window", [None, 5])
def test_differential_attention_is_two_softmax_maps_and_a_pair_norm(window):
    cfg, ops, w = _diff_operands()
    q1, q2, k1, k2, v = ops
    number = 15
    ctx = mixers.Ctx(rope=None, positions=None, window=window,
                     con=lambda t, *spec: t, layer=number)
    o, counters = mixers._diff_core(*ops, w, cfg, ctx)
    rep = lambda a: jnp.repeat(a, 2, axis=2)
    a1, a2 = (_maps(q, rep(k), rep(v), window)
              for q, k in ((q1, k1), (q2, k2)))
    init = 0.8 - 0.6 * math.exp(-0.3 * number)
    lq1, lk1, lq2, lk2 = w["lambdas"]
    lam = math.exp(float(lq1 @ lk1)) - math.exp(float(lq2 @ lk2)) + init
    d = a1 - lam * a2
    want = (1 - init) * d * jax.lax.rsqrt(
        jnp.mean(d * d, -1, keepdims=True) + 1e-5) * w["sub_norm"]
    assert o.shape == (2, T, 2, 16)
    assert float(jnp.abs(o - want).max()) < TOL
    assert float(counters["diff_lambda"]) == pytest.approx(lam, rel=1e-5)
    # the window is counted with the query's own position
    assert float(jnp.abs(a1 - _maps(q1, rep(k1), rep(v), None)).max()) > (
        1e-2 if window else -1)


def test_at_lambda_0_it_is_plain_attention_of_the_first_map():
    cfg, ops, w = _diff_operands()
    number = 17
    init = 0.8 - 0.6 * math.exp(-0.3 * number)
    second = jnp.full((8,), math.sqrt(math.log(1 + init) / 8))
    w = dict(w, lambdas=jnp.stack(          # exp(0) - (1 + init) + init
        [jnp.zeros((8,)), jnp.zeros((8,)), second, second]))
    ctx = mixers.Ctx(rope=None, positions=None, window=None,
                     con=lambda t, *spec: t, layer=number)
    o, counters = mixers._diff_core(*ops, w, cfg, ctx)
    assert abs(float(counters["diff_lambda"])) < 1e-6
    a1 = _maps(ops[0], jnp.repeat(ops[2], 2, 2), jnp.repeat(ops[4], 2, 2),
               None)
    want = (1 - init) * a1 * jax.lax.rsqrt(
        jnp.mean(a1 * a1, -1, keepdims=True) + 1e-5) * w["sub_norm"]
    assert float(jnp.abs(o - want).max()) < TOL


# -- what the comparison sees of a wrong memory -------------------------------------

@pytest.mark.parametrize("wrong", ["m from layer 14", "k, v from layer 15",
                                   "no D term", "lambda moved"])
def test_a_wrong_memory_or_mixer_reads_far_outside_the_tolerance(
        wrong, monkeypatch):
    """What no configuration field can break is held here: the program with
    the memory taken from the EARLIER writer, the ``D`` term off or a
    ``lambda`` vector moved differs from the reference by hundreds of
    times the tolerance the sound program is held to."""
    cfg, params, rows = make()
    broken = params
    if wrong == "m from layer 14":
        monkeypatch.setattr(transformer, "_writes", lambda c: (
            ("m",), (), (), ("kv",), (), (), (), ()))
    elif wrong == "k, v from layer 15":
        monkeypatch.setattr(transformer, "_writes", lambda c: (
            (), ("kv",), ("m",), (), (), (), (), ()))
    elif wrong == "no D term":
        ssm1 = dict(params["layers"]["ssm1"])
        ssm1["D"] = ssm1["D"].at[1].set(0.0)        # the writer's alone
        broken = dict(params, layers=dict(params["layers"], ssm1=ssm1))
    else:
        cross = dict(params["layers"]["cross"])
        cross["lambdas"] = cross["lambdas"].at[:, 0].add(1.0)
        broken = dict(params, layers=dict(params["layers"], cross=cross))
    z_p = jax.jit(lambda p, t: models.forward(p, t, cfg))(broken,
                                                          rows[:, :-1])
    z_r = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(z_p - z_r).max()) > 100 * TOL


# -- refusals -----------------------------------------------------------------------

@pytest.mark.parametrize("changes,named", [
    (dict(layer_mixers=("ssm1", "attn", "gmu", "cross", "ssm1", "attn", "gmu",
                        "cross"), first_layer=0),
     None),                                         # a sound order of its own
    (dict(layer_mixers=("gmu", "attn", "ssm1", "attn", "gmu", "cross", "gmu",
                        "cross")),
     r"layer_mixers\[0\] = 'gmu' reads the memory 'm', which no layer ahead "
     r"of it hands out \(a layer named one of \['ssm1'\]\)"),
    (dict(layer_mixers=("ssm1", "cross", "ssm1", "attn", "gmu", "cross",
                        "gmu", "cross")),
     r"layer_mixers\[1\] = 'cross' reads the memory 'kv'"),
    (dict(layer_mixers=("attn", "attn", "attn", "attn", "gmu", "cross", "gmu",
                        "cross")),
     r"layer_mixers\[4\] = 'gmu' reads the memory 'm'"),
    (dict(n_heads=3, n_kv_heads=3), "an odd number of heads"),
    (dict(n_kv_heads=1), "an odd number of heads"),
    (dict(diff_attn=False), "attn_bias is read by differential attention"),
    (dict(diff_attn=False, attn_bias=False),
     r"layer_mixers\[5\] = 'cross' needs diff_attn"),
    (dict(layer_pattern=((True, True),)), "heads it rotates"),
    (dict(ssm_dt_rank=0), r"layer_mixers\[0\] = 'ssm1' needs ssm_expand, "
                          "ssm_state, ssm_dt_rank"),
    (dict(ssm_expand=0), r"needs ssm_expand"),
    (dict(first_layer=None), "first_layer anchors a pattern"),
    (dict(first_layer=None, n_layers=32,
          layer_mixers=models.phi4_mini_flash_reasoning().layer_mixers),
     "a layer_pattern that no first_layer anchors"),
    (dict(arch="gpt2"), "arch='llama'"),
    (dict(sliding_window=None), "sliding_window is the width"),
])
def test_what_the_config_refuses_by_name_and_index(changes, named):
    cfg = replace(small(), **changes)
    if named is None:
        models.init_params(jax.random.PRNGKey(0), cfg)
        return
    with pytest.raises(ValueError, match=named):
        models.init_params(jax.random.PRNGKey(0), cfg)


def test_no_serving_path_runs_this_model():
    cfg = small()
    with pytest.raises(NotImplementedError) as said:
        models.init_kv_cache(cfg, 1, 8)
    for mechanism in ("selective-scan", "differential attention",
                      "gated memory unit", "'cross' layers"):
        assert mechanism in str(said.value)
    # the differential form alone, in a model of attention layers only
    plain = models.tiny(arch="llama", n_kv_heads=2, diff_attn=True,
                        attn_bias=True, layer_pattern=((False, False),))
    with pytest.raises(NotImplementedError, match="diff_attn"):
        transformer.refuse_decode(plain)
    with pytest.raises(NotImplementedError, match="layer_norm"):
        transformer.refuse_decode(models.tiny(arch="llama", layer_norm=True))


# -- scopes -------------------------------------------------------------------------

def test_the_new_parts_run_under_their_scopes():
    cfg, params, rows = make()
    text = jax.jit(lambda p, t: models.forward(p, t, cfg)).lower(
        params, rows[:, :-1]).as_text(debug_info=True)
    for scope in ("attn/attn_linear/attn_qkv", "attn/attn_linear/kda_conv",
                  "attn/attn_linear/kda_gate", "attn/attn_linear/attn_core",
                  "attn/attn_linear/attn_out", "attn/attn_linear/gmu",
                  "attn/attn_window/attn_core", "attn/attn_window/attn_diff",
                  "attn/attn_full/attn_diff", "attn/attn_full/attn_gqa",
                  "attn/attn_full/attn_cross/attn_core",
                  "attn/attn_full/attn_cross/attn_diff",
                  "attn/attn_full/attn_cross/attn_qkv",
                  "attn/attn_full/attn_out"):
        assert scope in text, scope
    assert "attn_linear/gmu/attn_core" not in text  # no scan in a memory unit
    assert {mixers.DIFF_SCOPE, mixers.CROSS_SCOPE, mixers.GMU_SCOPE} == {
        "attn_diff", "attn_cross", "gmu"}
    assert transformer.SCOPE_FILES[-1] == mixers.__file__
    counters = {k.metric for k in transformer._counters(cfg)}
    assert counters == {"attn_diff_lambda", "gmu_gate_mean",
                        "kda_log_decay_min", "ssm_step_mean"}
    assert transformer._counters(cfg)[mixers.DIFF_LAMBDA] == (1, 3, 5, 7)


def _barriers(cfg, params, rows) -> int:
    """``optimization_barrier``s in the forward alone, which has no remat."""
    jaxpr = jax.make_jaxpr(lambda p, t: models.forward(p, t, cfg))(
        params, rows[:, :-1])
    return str(jaxpr).count("optimization_barrier")


def test_the_stream_is_cut_between_a_layers_halves_where_memory_is_carried():
    """A model that hands out memory runs in line, and there every block's
    two halves read ONE rounded stream (``_block``'s ``cut``; PERF.md section
    6, PR 62): a barrier a layer. A model with no reader has none, so every
    other model's step stays what it was."""
    cfg, params, rows = make(remat=False)
    assert _barriers(cfg, params, rows) == cfg.n_layers
    plain = small(n_layers=2, first_layer=14, remat=False)   # ssm1, window
    assert not any(transformer._writes(plain))
    assert _barriers(plain, models.init_params(jax.random.PRNGKey(0), plain),
                     rows) == 0


def test_the_loss_on_a_mesh_is_the_unsharded_one():
    """(data 2, fsdp 2, tensor 2) on the CPU's virtual devices: attention's
    and the cross layers' projections shard by head (the pairs are split
    out of sharded weights), the scan's and the memory units' leaves are
    replicated (ROADMAP B3)."""
    from jax.sharding import PartitionSpec as P

    cfg, params, rows = make()
    specs, _ = sm.sharded_loss_is_the_unsharded(cfg, params, rows, 5 * TOL)
    by_head = P(None, None, "tensor", None)
    assert specs["layers"]["cross"]["wq"] == by_head == specs["layers"][
        "mha"]["wk"]
    assert specs["layers"]["attn"]["wo"] == P(None, "tensor", None, None)
    assert all(s is None for s in jax.tree.leaves(
        (specs["layers"]["ssm1"], specs["layers"]["gmu"]),
        is_leaf=lambda s: s is None))
