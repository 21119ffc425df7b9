"""SmallThinker on the normal path against its plain reference
(``chipbench/reference/smallthinker.py``), at a SmallThinker-shaped small
size on the CPU: one period of four layers (global NoPE, then three
windowed with RoPE), hidden 64, 4 query heads on 2 key / value heads of 32
(so ``head_dim`` is NOT ``d_model / n_heads``), 8 ReGLU experts of width
32, top 3, dropless, gates a softmax over the chosen logits, the router
reading the FIRST norm, balance loss; a window of 16 keys on rows of 64.
The parameters hold rank 1 of 4's experts (2 of the 8) unless a test says
otherwise.

Weights: as in ``tests/test_olmoe.py``, the layer weights are drawn at
``SCALE`` x the program's N(0, 0.02) and the router at 10 x that again,
so that every branch moves the logits and routing is uneven. Both sides
compute in float32: the tolerances are float32 rounding grown by the
depth of the sums; a fault has to miss by 100 x that.
"""

from __future__ import annotations

import functools
import importlib
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from chipbench.reference import _common
from chipbench.reference import smallthinker as reference
from ray_tpu import models
from ray_tpu.models import transformer
from ray_tpu.ops import moe

import _small_models as sm

TOL = 2e-5
T, WINDOW, E, RANKS = 64, 16, 8, 4


def small(**kw):
    base = dict(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_head=32, d_ff=32,
        n_experts=E, expert_top_k=3, vocab_size=256, max_seq_len=T,
        sliding_window=WINDOW, experts_held=(1, RANKS), dtype="float32")
    base.update(kw)
    return models.smallthinker_21b_a3b(**base)


def make(seed: int = 0, **kw):
    """(cfg, params, rows [2, T + 1])."""
    return sm.make(small, seed, tokens=T, **kw)


# the reference's layer, compiled once a kind of layer as its ``_run`` does
reference_layer = jax.jit(reference._layer, static_argnums=tuple(range(2, 8)))


def held_slice(params, cfg, held):
    """``params`` (of a cfg that holds every expert) cut to ``held`` =
    (rank, of)'s experts."""
    first, end = moe.held_range(cfg.n_experts, *held)
    mlp = jax.tree.map(lambda a: a[:, first:end], params["layers"]["mlp"])
    return dict(params, layers=dict(params["layers"], mlp=mlp))


# -- the preset ---------------------------------------------------------------

def test_preset_is_smallthinker_as_published():
    c = models.smallthinker_21b_a3b()
    published = spec.load_json(
        "chipbench", "configs", "smallthinker-21b-a3b-ep4.json")
    p = dict(published, **published["published"])
    assert (c.vocab_size, c.n_layers, c.d_model, c.n_heads, c.kv_heads,
            c.head_dim, c.ffn_dim, c.n_experts, c.expert_top_k,
            c.max_seq_len, c.rope_theta, c.norm_eps, c.sliding_window) == (
        p["vocab_size"], p["num_hidden_layers"], p["hidden_size"],
        p["num_attention_heads"], p["num_key_value_heads"], p["head_dim"],
        p["moe_ffn_hidden_size"], p["moe_num_primary_experts"],
        p["moe_num_active_primary_experts"], p["max_position_embeddings"],
        p["rope_theta"], p["rms_norm_eps"], p["sliding_window_size"])
    assert (c.n_layers, c.d_model, c.n_heads * c.head_dim) == (52, 2560, 3584)
    kinds = [c.layer_kind(i) for i in range(c.n_layers)]
    assert [int(w) for w, _ in kinds] == p["sliding_window_layout"]
    assert [int(r) for _, r in kinds] == p["rope_layout"]
    assert (c.arch, c.tied, c.qk_norm, c.expert_norm_topk) == (
        "llama", False, False, True)
    assert (c.expert_activation, c.router_input) == ("relu", "attn_norm")
    assert c.expert_capacity_factor is None and c.experts_held is None
    assert (c.router_aux_weight, c.router_z_weight) == (0.01, 0.0)
    d = models.TransformerConfig()      # the step's options are defaults
    assert (c.attn_impl, c.remat, c.remat_policy, c.loss_chunk) == (
        d.attn_impl, d.remat, d.remat_policy, d.loss_chunk)
    assert 21.4e9 < c.num_params() < 21.6e9             # "21B"
    cell = spec.model_config(published)
    assert cell.num_params() == 656_529_920 and cell.experts_here == 16


def test_presets_without_a_pattern_scan_single_layers_as_before():
    """A period of one is the scan over the stacked layers itself: no
    reshape of the weights, one scan of ``n_layers`` steps."""
    for cfg in (models.tiny(arch="llama"), models.tiny()):
        params = models.init_params(jax.random.PRNGKey(0), cfg)
        jaxpr = jax.make_jaxpr(lambda p, t: models.forward(p, t, cfg))(
            params, jnp.zeros((1, 8), jnp.int32))
        scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
        assert [e.params["length"] for e in scans] == [cfg.n_layers]
        assert not any(e.primitive.name == "reshape" and len(
            e.outvars[0].aval.shape) > 4 for e in jaxpr.eqns)
    cfg, params, rows = make(n_layers=8)
    jaxpr = jax.make_jaxpr(lambda p, t: models.forward(p, t, cfg))(
        params, rows[:, :-1])
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [2]     # whole periods


# -- program = reference --------------------------------------------------------

def test_every_branch_moves_the_logits():
    """The scale of this file's weights: zeroing the held experts' output
    moves the logits far over the tolerance, so a fault cannot hide."""
    cfg, params, rows = make()
    want = sm.forward(params, rows[:, :-1], cfg)
    mlp = dict(params["layers"]["mlp"],
               w_down=params["layers"]["mlp"]["w_down"] * 0.0)
    off = sm.forward(dict(params, layers=dict(params["layers"], mlp=mlp)),
                     rows[:, :-1], cfg)
    assert float(jnp.abs(want - off).max()) > 1000 * TOL


@pytest.mark.parametrize("seed,held", [(0, (1, 4)), (1, (3, 4)), (2, (0, 2)),
                                       (3, None)])
def test_program_equals_reference_logits_loss_and_gradients(seed, held):
    cfg, params, rows = make(seed, experts_held=held)
    got = sm.forward(params, rows[:, :-1], cfg)
    want = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(got - want).max()) < TOL
    (loss, metrics), g = sm.loss_metrics_and_grads(params, rows, cfg)
    reference_loss, r = sm.value_and_grad(reference.loss, cfg)(params, rows)
    assert float(loss) == pytest.approx(float(reference_loss), abs=TOL)
    # the whole loss is cross entropy + 0.01 x balance over all 8 experts
    rest = 0.01 * float(metrics["router_aux"])
    assert rest > 0.0099 and float(metrics["router_z"]) > 0.0
    ce = -_common.token_logprobs(want, rows[:, 1:]).mean()
    assert float(loss) - rest == pytest.approx(float(ce), abs=TOL)
    assert ("moe_held_share" in metrics) == (held is not None)
    assert ("moe_full_buffer" in metrics) == (held is not None)
    g, r = g["layers"], r["layers"]
    for got_g, want_g in ((g["mlp"]["w_gate"][1, 0], r["mlp"]["w_gate"][1, 0]),
                          (g["mlp"]["w_down"][0, 1], r["mlp"]["w_down"][0, 1]),
                          (g["router"]["w"], r["router"]["w"]),
                          (g["attn"]["wk"][0], r["attn"]["wk"][0]),
                          (g["attn"]["wq"][2], r["attn"]["wq"][2])):
        assert float(jnp.abs(want_g).max()) > 1e-4      # not a dead branch
        np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                                   rtol=1e-3, atol=1e-5)


def test_unrolled_and_rematted_layers_are_the_same_model():
    cfg, params, rows = make()
    want = sm.forward(params, rows[:, :-1], cfg)
    for changes in (dict(scan_layers=False), dict(remat=False),
                    dict(remat_policy="dots")):
        got = sm.forward(params, rows[:, :-1], replace(cfg, **changes))
        assert float(jnp.abs(got - want).max()) < TOL, changes


# -- the four faults ------------------------------------------------------------

ALL_ROPE = ((False, True), (True, True), (True, True), (True, True))
# what is broken -> (the program's config changes, experts the program holds)
FAULTS = {
    "the window ignored on a windowed layer": (dict(sliding_window=10 * T), None),
    "RoPE applied on a global layer": (dict(layer_pattern=ALL_ROPE), None),
    "an absent expert's output added": (dict(experts_held=(0, 2)), (0, 2)),
    "SiLU in place of ReLU": (dict(expert_activation="silu"), None),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_a_fault_fails_the_comparison(name):
    """Each case breaks one thing in the program; the comparison of
    ``test_program_equals_reference...`` (logits within ``TOL``) has to
    fail by a wide margin. The reference holds rank 0 of 4's experts (0
    and 1); "an absent expert's output added" is a program that holds
    rank 0 of TWO's (0 to 3) and so adds experts 2 and 3, which the
    reference's rank does not have."""
    changes, program_holds = FAULTS[name]
    cfg, full, rows = make(experts_held=None)
    ref_cfg = replace(cfg, experts_held=(0, 4))
    want = reference.forward(held_slice(full, cfg, (0, 4)), rows[:, :-1],
                             ref_cfg)
    run_cfg = replace(ref_cfg, **changes)
    params = held_slice(full, cfg, program_holds or (0, 4))
    right = sm.forward(held_slice(full, cfg, (0, 4)), rows[:, :-1], ref_cfg)
    assert float(jnp.abs(right - want).max()) < TOL
    got = sm.forward(params, rows[:, :-1], run_cfg)
    assert float(jnp.abs(got - want).max()) > 100 * TOL, name


# -- the shares add up ----------------------------------------------------------

def _one_layer(x, lp, cfg, kind):
    return transformer._block(x, lp, cfg, rope=None if not kind[1] else (
        transformer.rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                     theta=cfg.rope_theta)),
        con=lambda t, *spec: t, kind=kind)[0]


@pytest.mark.parametrize("layer", [0, 1])
def test_the_four_ranks_shares_sum_to_the_uncut_layer(layer):
    """One layer (global, then windowed) on the same input: each rank's
    program block gives ``h + its experts' part``; what every rank
    computes alike (``h``: attention and the residual) counted once, the
    four parts sum to the UNCUT reference's layer, which holds all 8
    experts."""
    cfg, full, rows = make(experts_held=None)
    x = full["embed"]["tokens"][rows[:, :-1]] * 10.0
    lp = _common.layer_slice(full["layers"], layer)
    kind = cfg.layer_kind(layer)
    args = (cfg.n_heads, float(cfg.rope_theta), cfg.expert_top_k,
            cfg.sliding_window if kind[0] else None, kind[1], 0)
    with jax.default_matmul_precision("highest"):
        sm.ranks_parts_sum_to_the_uncut_layer(
            x, lp, cfg, RANKS, lambda x, lp: reference_layer(x, lp, *args)[0],
            lambda x, lp, cfg: _one_layer(x, lp, cfg, kind), TOL)


# -- dropless under skew, with held experts ---------------------------------------

def _block_inputs(seed=0, n=96, d=16, f=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (1, n, d))
    w_gate = jax.random.normal(keys[1], (E, d, f)) * 0.3
    w_up = jax.random.normal(keys[2], (E, d, f)) * 0.3
    w_down = jax.random.normal(keys[3], (E, f, d)) * 0.3
    noise = jax.random.normal(keys[4], (1, n, E))
    return x, w_gate, w_up, w_down, noise


def _plain_held(x, logits, w_gate, w_up, w_down, held, top_k):
    """Every token through every HELD expert, gates zero elsewhere."""
    top_r, top_e = jax.lax.top_k(logits, top_k)
    g = jax.nn.softmax(top_r, -1)
    gates = (jax.nn.one_hot(top_e, E) * g[..., None]).sum(-2)     # [.., E]
    out = jnp.zeros_like(x)
    for e in range(*held):
        act = jax.nn.relu(x @ w_gate[e]) * (x @ w_up[e])
        out = out + gates[..., e, None] * (act @ w_down[e])
    return out


def _held_by(both: int, one: int = 0):
    """Logits under which the first ``both`` tokens choose held experts 2
    AND 3, the next ``one`` tokens expert 2 and an absent one, and every
    other token no held expert: 2 x ``both`` + ``one`` held assignments
    where ``top_k`` is 2."""
    def skew(noise):
        token = jnp.arange(noise.shape[1])[None, :, None]
        noise = noise.at[..., 2:4].add(jnp.where(token < both, 20.0, -20.0))
        return noise.at[..., 2:3].add(jnp.where(
            (token >= both) & (token < both + one), 40.0, 0.0))
    return skew


# name: (tokens, top_k, the logits from the noise, ``full_buffer``). Two of
# eight experts are held, so the row buffer is twice a quarter of the
# assignments in whole 512-row tiles: all 288 of 96 x 3, 1024 of 1024 x 2.
SKEWS = {
    # every token's three choices are held experts 2, 3 and one more
    "all on held experts": (
        96, 3, lambda noise: noise.at[..., 2:4].add(20.0), 0.0),
    "all on ONE held expert first": (
        96, 3, lambda noise: noise.at[..., 3].add(20.0), 0.0),
    # no token chooses a held expert at all
    "all on absent experts": (
        96, 3, lambda noise: noise.at[..., 2:4].add(-20.0), 0.0),
    "as the noise falls": (96, 3, lambda noise: noise, 0.0),
    "held total under the buffer": (1024, 2, lambda noise: noise, 0.0),
    "held total fills the buffer": (1024, 2, _held_by(512), 0.0),
    "held total one over the buffer": (1024, 2, _held_by(512, 1), 1.0),
    "every assignment held": (1024, 2, _held_by(1024), 1.0),
    "no assignment held": (1024, 2, _held_by(0), 0.0),
}
HELD_TOTALS = {"held total fills the buffer": 1024,
               "held total one over the buffer": 1025,
               "every assignment held": 2048, "no assignment held": 0}
HELD = (2, 4)


def _held_block(x, logits, *weights, top_k):
    return moe.moe_swiglu_dropless(
        x, None, *weights, top_k=top_k, router_logits=logits, held=HELD,
        activation="relu")


def _plain_block(x, logits, *weights, top_k):
    """``_plain_held`` on the held experts' ``weights`` among zeros."""
    whole = [jnp.zeros((E,) + w.shape[1:], w.dtype).at[HELD[0]:HELD[1]].set(w)
             for w in weights]
    return _plain_held(x, logits, *whole, HELD, top_k), None


@functools.cache
def _block_calls(block, top_k, whole_buffer=False):
    """(``block``, the gradient of its output's squares by its five
    inputs), compiled as a step is (op by op rounds apart) and once a
    shape for all the skews. ``whole_buffer``: the CALLER has patched
    ``moe._buffer_rows`` to give every row."""
    def out(*a):
        return block(*a, top_k=top_k)

    return jax.jit(out), jax.jit(jax.grad(
        lambda *a: (out(*a)[0] ** 2).sum(), range(5)))


@pytest.mark.parametrize("skew", list(SKEWS))
def test_dropless_with_held_experts_under_skew(skew, monkeypatch):
    """Rank 1 of 4 holds experts 2 and 3. Whatever share of the
    ``top_k x tokens`` assignments lands on them (all of them, none), each
    is computed, nothing is dropped, the absent ones add nothing, and the
    counters say what happened. The row buffer is twice the rank's
    balanced share and the layer takes it a round at a time: where the
    held assignments fit one round (to the row), the output, the
    gradients of x and of the weights and the counters EQUAL, bit for
    bit, those of the block traced over all ``top_k x tokens`` rows at
    once; where they take a second round (``full_buffer``), they agree to
    float32 rounding."""
    tokens, top_k, skewed, full_buffer = SKEWS[skew]
    x, w_gate, w_up, w_down, noise = _block_inputs(n=tokens)
    logits, held = skewed(noise), HELD
    weights = [w[held[0]:held[1]] for w in (w_gate, w_up, w_down)]
    assert moe._buffer_rows(tokens * top_k, 2, E) == {96: 288, 1024: 1024}[
        tokens]

    run, grads_of_run = _block_calls(_held_block, top_k)
    out, stats = run(x, logits, *weights)
    want = _plain_held(x, logits, w_gate, w_up, w_down, held, top_k)
    assert float(jnp.abs(out - want).max()) < TOL
    chosen = np.asarray(jax.lax.top_k(logits, top_k)[1]).reshape(-1)
    counts = np.bincount(chosen, minlength=E)
    here = counts[held[0]:held[1]]
    assert here.sum() == HELD_TOTALS.get(skew, here.sum())
    assert float(stats["held_share"]) == pytest.approx(
        here.sum() / chosen.size)
    assert float(stats["full_buffer"]) == full_buffer
    if here.sum():
        assert float(stats["load_max"]) == pytest.approx(
            here.max() / here.mean())
    if skew == "all on held experts":
        assert here.sum() >= 2 * x.shape[1]         # 2 of 3 choices, or more
    if skew in ("all on absent experts", "no assignment held"):
        assert here.sum() == 0 and float(jnp.abs(out).max()) == 0.0
    # gradients: jax's own through the plain block
    grads = grads_of_run(x, logits, *weights)
    plain = _block_calls(_plain_block, top_k)[1](x, logits, *weights)
    for got, want_g in zip(grads, plain):
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_g),
                                   rtol=1e-3, atol=1e-5)
    # the same block over all ``top_k x tokens`` rows at once, whatever is
    # held: EQUAL where the held rows take one round of the buffer; where
    # they take two, a token's choices are summed round by round
    monkeypatch.setattr(moe, "_buffer_rows", lambda rows, held, of: rows)
    run, grads_of_run = _block_calls(_held_block, top_k, whole_buffer=True)
    full_out, full_stats = run(x, logits, *weights)
    same = (np.testing.assert_array_equal if not full_buffer else
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-5 * float(np.abs(b).max())))
    same(np.asarray(out), np.asarray(full_out))
    assert float(full_stats.pop("full_buffer")) == 0.0
    for name, value in full_stats.items():
        np.testing.assert_array_equal(np.asarray(stats[name]),
                                      np.asarray(value), err_msg=name)
    x_g, logits_g, *weight_gs = zip(grads, grads_of_run(x, logits, *weights))
    for got, full in (x_g, *weight_gs):
        same(np.asarray(got), np.asarray(full))
    # the gates' gradient <h, dh_u> is a sum of ``d_ff`` products that
    # XLA's CPU compiler contracts one way in a loop and another outside
    # one: the last place of float32, not the rows
    np.testing.assert_allclose(*map(np.asarray, logits_g), rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(logits_g[1]).max()))


def _held_counts(cfg, params, rows):
    """[layers, held experts] assignments, by walking the reference's
    layers over ``rows``."""
    first, end = cfg.held_range
    counts = []
    x = params["embed"]["tokens"][rows[:, :-1]]
    for i in range(cfg.n_layers):
        lp = _common.layer_slice(params["layers"], i)
        r = reference._rms(x, lp["ln1"]["w"]).reshape(
            -1, cfg.d_model) @ lp["router"]["w"]
        chosen = np.asarray(jax.lax.top_k(r, cfg.expert_top_k)[1]).reshape(-1)
        counts.append(np.bincount(chosen, minlength=cfg.n_experts)[first:end])
        windowed, with_rope = cfg.layer_pattern[i % len(cfg.layer_pattern)]
        x, _ = reference_layer(
            x, lp, cfg.n_heads, float(cfg.rope_theta), cfg.expert_top_k,
            cfg.sliding_window if windowed else None, bool(with_rope), first)
    return np.stack(counts)


@pytest.mark.parametrize("seed,held", [(0, (1, 4)), (4, (3, 4))])
def test_the_train_steps_counters_count_the_whole_batch(seed, held):
    """``moe_held_share``, ``moe_load_max`` and ``moe_full_buffer`` in the
    TRAIN STEP's metrics dict (what a driver that fetched more than the
    loss would read; the benchmark's fetches the loss alone) against
    counts made by walking the reference's layers over both rows: the
    mean over the layers of the share that went to a held expert, the
    fullest held expert over the held mean in the fullest layer, and the
    share of the layers whose held assignments overflow the row buffer
    (none: at 384 assignments the buffer is all of them). A model that
    holds every expert reports neither the share nor the buffer."""
    cfg, params, rows = make(seed, experts_held=held)
    opt = sm.adamw(3e-4)
    _, metrics = sm.train_step(cfg, opt)(sm.train_state(params, opt),
                                         {"tokens": rows})
    here = _held_counts(cfg, params, rows)
    assignments = rows[:, :-1].size * cfg.expert_top_k
    shares, fullest = here.sum(1) / assignments, here.max(1) / here.mean(1)
    assert float(metrics["moe_held_share"]) == pytest.approx(
        np.mean(shares), abs=1e-6)
    assert float(metrics["moe_load_max"]) == pytest.approx(
        max(fullest), rel=1e-5)
    assert len(set(np.round(shares, 4))) > 1 and max(fullest) > 1.1   # uneven
    assert moe._buffer_rows(assignments, here.shape[1],
                            cfg.n_experts) == assignments
    assert float(metrics["moe_full_buffer"]) == 0.0
    whole = replace(cfg, experts_held=None)
    _, metrics = sm.train_step(whole, opt)(
        sm.train_state(models.init_params(jax.random.PRNGKey(0), whole), opt),
        {"tokens": rows})
    assert not {"moe_held_share", "moe_full_buffer"} & set(metrics)
    assert "moe_load_max" in metrics


@functools.cache
def _patched_step(cfg, room):
    """The loss, its metrics and its gradients under ``moe._HELD_ROOM`` =
    ``room`` (None: ``moe._buffer_rows`` gives every row), which the CALLER
    has patched: the patch is in this key because it is in no key of
    ``_small_models``."""
    return jax.jit(jax.value_and_grad(lambda p, rows: models.lm_loss(
        p, {"tokens": rows}, cfg), has_aux=True))


@pytest.mark.parametrize("seed,room", [(0, 2), (4, 2), (4, 1)])
def test_the_short_row_buffer_is_the_same_model(seed, room, monkeypatch):
    """At 2 x 256 tokens the row buffer (two tiles of 512 rows for the 2
    held of 8 experts) is shorter than the 1536 assignments: the loss and
    every gradient are those of the model traced with the full buffer
    alone, and ``moe_full_buffer`` is the share of the layers whose held
    assignments do not fit. With room for the balanced share only (one
    tile) seed 4's skewed routers overflow it in some layers."""
    cfg, params, _ = make(seed, max_seq_len=256)
    rows = jax.random.randint(jax.random.PRNGKey(seed + 2000), (2, 257), 0,
                              cfg.vocab_size)
    monkeypatch.setattr(moe, "_HELD_ROOM", room)
    buffer = moe._buffer_rows(512 * cfg.expert_top_k, 2, cfg.n_experts)
    assert buffer == 512 * room

    (loss, metrics), grads = _patched_step(cfg, room)(params, rows)
    over = _held_counts(cfg, params, rows).sum(1) > buffer
    assert float(metrics["moe_full_buffer"]) == over.mean()
    assert over.any() == (room == 1) and not over.all()
    monkeypatch.setattr(moe, "_buffer_rows", lambda rows, held, of: rows)
    (full_loss, full_metrics), full_grads = _patched_step(cfg, None)(
        params, rows)
    assert float(full_metrics["moe_full_buffer"]) == 0.0
    assert float(loss) == pytest.approx(float(full_loss), abs=1e-6)
    for got, full in zip(jax.tree.leaves(grads), jax.tree.leaves(full_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=1e-4, atol=1e-6)


def test_held_range_and_what_the_config_refuses():
    assert moe.held_range(64, 0, 4) == (0, 16)
    assert moe.held_range(64, 3, 4) == (48, 64)
    with pytest.raises(ValueError, match="do not divide"):
        moe.held_range(64, 0, 5)
    with pytest.raises(ValueError, match="dropless"):
        models.init_params(jax.random.PRNGKey(0),
                           small(expert_capacity_factor=1.25))
    with pytest.raises(ValueError, match="whole number of periods"):
        models.init_params(jax.random.PRNGKey(0), small(n_layers=6))
    with pytest.raises(ValueError, match="sliding_window"):
        models.init_params(jax.random.PRNGKey(0), small(sliding_window=None))
    with pytest.raises(ValueError, match="arch='llama'"):
        models.init_params(jax.random.PRNGKey(0), models.tiny(d_head=8))


def test_no_serving_path_runs_this_model():
    """The KV-cache decode and the slot engine run one kind of dense
    layer: they refuse, by name, what they would run wrongly in silence."""
    cfg, params, rows = make()
    with pytest.raises(NotImplementedError, match="MoE"):
        models.init_kv_cache(cfg, 1, 32)
    dense = models.tiny(arch="llama")
    for field, value in (
            ("layer_pattern", ((False, False), (True, True))),
            ("sliding_window", 16)):
        bad = replace(dense, **{field: value})
        with pytest.raises(NotImplementedError, match=field):
            models.init_kv_cache(bad, 1, 32)
        with pytest.raises(NotImplementedError, match=field):
            models.decode_step(None, jnp.zeros((1, 1), jnp.int32),
                               {"pos": jnp.zeros((), jnp.int32)}, bad)
    from ray_tpu.llm import engine

    source = importlib.import_module("inspect").getsource(
        engine.LLMEngine.__init__)
    assert "tfm.refuse_decode(c)" in source


def test_dropless_on_an_expert_mesh_is_still_refused_by_name():
    from ray_tpu.parallel.mesh import MeshConfig

    cfg, params, rows = make()
    mesh = MeshConfig(data=2, expert=4).build()
    with pytest.raises(NotImplementedError, match="all-to-all"):
        models.forward(params, rows[:, :-1], cfg, mesh=mesh)


# -- scopes -----------------------------------------------------------------------

def test_the_new_scopes_are_on_the_instructions():
    """``attn_full`` / ``attn_window`` inside ``attn``, and the router's
    matmul under ``moe`` / ``moe_router`` although it runs ahead of
    attention; a model with no pattern opens neither attention scope."""
    cfg, params, rows = make()
    text = sm.jitted(models.forward, cfg).lower(
        params, rows[:, :-1]).as_text(debug_info=True)
    for path in ("attn/attn_full", "attn/attn_window", "moe/moe_router",
                 "moe/moe_experts"):
        assert path in text, path
    dense = models.tiny(arch="llama")
    text = sm.jitted(models.forward, dense).lower(
        models.init_params(jax.random.PRNGKey(0), dense),
        rows[:, :-1]).as_text(debug_info=True)
    assert "attn_full" not in text and "attn_window" not in text
