"""The seam for a token mixer (``ray_tpu/models/mixers.py``): ONE record a
kind, and a counter named once.

(a) each of the four records against the preset that uses it: the leaves
its ``init`` draws are the subtree the model keeps and the subtree
``partition_specs`` names, and its ``check`` refuses its own missing field
by today's words; (b) ONE MORE mixer that only this file knows (a causal
cumulative mean with one learned scale a layer, one counter folded by
``max``; it writes no memory and its ``apply`` returns the two values it
always did) trains beside attention through ``make_train_step`` with
nothing in ``transformer.py`` edited; (c) ``init_params`` at seed 0 gives, for the
seven architectures' small models and three presets, the bytes the tree
before the seam gave (PR 59's, recorded from its checkout); (d) every
counter of the tables reaches ``lm_loss``'s metrics under its name, for the
small model that has the mechanism. The small models are the
per-architecture files' own (``tests/_small_models.py``)."""

from __future__ import annotations

import functools
import hashlib
import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import _small_models as sm
from ray_tpu import models
from ray_tpu.models import mixers, transformer
from test_kanana2 import small as kanana2
from test_kimi_linear import small as kimi_linear
from test_nemotron_h import small as nemotron_h
from test_olmoe import small as olmoe
from test_qwen3_next import small as qwen3_next
from test_smallthinker import small as smallthinker
from test_trinity_mini import small as trinity_mini

SMALL = {"olmoe": olmoe, "smallthinker": smallthinker, "kanana2": kanana2,
         "kimi_linear": kimi_linear, "trinity_mini": trinity_mini,
         "qwen3_next": qwen3_next, "nemotron_h": nemotron_h,
         "tiny": models.tiny, "tiny_moe": models.tiny_moe,
         "gpt2_small": models.gpt2_small}


# -- (a) a record against the preset that uses it ------------------------------

def _draw(cfg, seed: int = 0) -> mixers.Draw:
    """A ``Draw`` of the test's own: what ``init_params`` hands a record."""
    pdt = jnp.dtype(cfg.param_dtype)
    return mixers.Draw(
        norm=lambda key, *shape, s=0.02: (
            jax.random.normal(key, shape, jnp.float32) * s).astype(pdt),
        uniform=lambda key, *shape, low, high: jax.random.uniform(
            key, shape, jnp.float32, low, high),
        unit=lambda *shape: jnp.ones(shape, pdt), res_std=0.01,
        gate_keys=iter(jax.random.split(jax.random.PRNGKey(seed + 1), 2)))


@pytest.mark.parametrize("kind,name", [
    ("attn", "olmoe"), ("attn", "kanana2"), ("attn", "trinity_mini"),
    ("kda", "kimi_linear"), ("gdn", "qwen3_next"), ("ssm", "nemotron_h")])
def test_a_records_init_and_specs_mirror_the_models_subtree(kind, name):
    cfg = SMALL[name]()
    mixer = mixers.MIXERS[kind]
    n = len(cfg.layers_with(kind)) - sum(
        i < cfg.n_dense_layers for i in cfg.layers_with(kind))
    assert n > 0
    drawn = jax.eval_shape(lambda: mixer.init(
        cfg, iter(jax.random.split(jax.random.PRNGKey(0), 32)), n,
        _draw(cfg)))
    kept = dict(cfg.shapes()["layers"][mixer.stack(cfg)])
    if cfg.layer_mixers:        # ``attn/wo`` is every mixer layer's
        drawn.pop("wo", None)
    assert {k: (v.shape, v.dtype) for k, v in drawn.items()} == {
        k: (v.shape, v.dtype) for k, v in kept.items()}
    specs = mixer.specs(cfg)
    named = models.partition_specs(cfg)["layers"][mixer.stack(cfg)]
    assert set(named) == set(kept)
    for leaf, shape in kept.items():
        assert named[leaf] == specs.get(leaf), leaf
        assert named[leaf] is None or len(named[leaf]) == len(shape.shape)
    assert any(isinstance(s, P) for s in named.values())
    assert transformer._holds(cfg, mixer.stack(cfg), cfg.layer_kind(
        cfg.layers_with(kind)[-1]))


@pytest.mark.parametrize("kind,name,changes,said", [
    ("kda", "kimi_linear",
     dict(kv_latent=None, d_head_nope=0, d_head_rope=0, d_head_v=0,
          latent_rope=True),
     "layer_mixers does not run with 'kda' beside attention that is not "
     r"latent \(kv_latent\)"),
    ("kda", "kimi_linear", dict(kda_heads=0),
     "layer_mixers does not run with kda_heads, kda_head_dim or kda_conv "
     "< 1"),
    ("kda", "kimi_linear", dict(kda_heads=2),
     r"KDA heads \(2, 16\) that are not attention's \(n_heads, a value's "
     r"width\) \(4, 16\)"),
    ("gdn", "qwen3_next",
     dict(kv_latent=16, d_head_nope=8, d_head_rope=8, d_head_v=8,
          qk_norm=False, n_kv_heads=4, attn_gate=False, rope_fraction=1.0),
     r"layer_mixers does not run with 'gdn' beside latent attention "
     r"\(kv_latent\)"),
    ("gdn", "qwen3_next", dict(kda_heads=6, linear_key_heads=3),
     r"Gated DeltaNet heads \(6, 16\) that are not attention's"),
    ("ssm", "nemotron_h", dict(ssm_state=0),
     r"layer_mixers\[0\] = 'ssm' needs ssm_state >= 1, ssm_chunk >= 1 and "
     "ssm_groups that divide kda_heads"),
    ("ssm", "nemotron_h", dict(kda_heads=8),
     r"state-space heads \(8, 8\) that are not attention's"),
])
def test_a_records_check_refuses_its_own_field_by_name(kind, name, changes,
                                                       said):
    cfg = SMALL[name](**changes)
    need, rows = mixers.MIXERS[kind].check(cfg)
    assert (need is not None and not need[1]) or any(w for _, w in rows)
    with pytest.raises(ValueError, match=said):
        transformer._check_config(cfg)
    # ... and nothing of a sound one
    need, rows = mixers.MIXERS[kind].check(SMALL[name]())
    assert (need is None or need[1]) and not any(w for _, w in rows)


def test_the_names_other_modules_import_are_still_transformers():
    assert transformer.MIXERS == tuple(mixers.MIXERS) == (
        "attn", "kda", "gdn", "ssm", "ssm1", "gmu", "cross")
    assert transformer.LINEAR_MIXERS == ("kda", "gdn", "ssm", "ssm1")
    assert transformer.FFN_ONLY == "ffn"
    assert transformer._expand_gqa is mixers._expand_gqa
    assert transformer.SCOPE_FILES[-1] == mixers.__file__
    assert mixers.MIXERS["attn"].decodes is None
    for kind in transformer.LINEAR_MIXERS:
        assert mixers.MIXERS[kind].scope(None) == "attn_linear"
        assert mixers.MIXERS[kind].stack(None) == kind


# -- (b) one more mixer, registered from outside ----------------------------------

PEAK = mixers.Counter("cummean_peak", "cummean_out_absmax", "max")


def _cummean(h, w, c, ctx):
    """The mean of the tokens so far, times one learned scale a layer ->
    (o FLAT [B, T, D], {the largest |o|})."""
    with jax.named_scope("attn_core"):
        steps = jnp.arange(1, h.shape[1] + 1, dtype=h.dtype)[None, :, None]
        o = jnp.cumsum(h, axis=1) / steps * w["scale"].astype(h.dtype)
    return o, {PEAK.key: jnp.abs(jax.lax.stop_gradient(o)).max().astype(
        jnp.float32)}


CUMMEAN = mixers.Mixer(
    stack=lambda c: "cummean", scope=lambda window: "attn_linear",
    init=lambda c, keys, n, draw: {
        "scale": jnp.ones((n, 1), jnp.dtype(c.param_dtype))},
    specs=lambda c: {"scale": P(None, None)}, check=lambda c: (None, ()),
    apply=_cummean, counters=lambda c: (PEAK,), decodes=None)


def test_a_fifth_mixer_trains_beside_attention_with_no_edit(monkeypatch):
    monkeypatch.setitem(mixers.MIXERS, "cummean", CUMMEAN)
    cfg = models.tiny(arch="llama", n_layers=4, n_kv_heads=2,
                      dtype="float32",
                      layer_mixers=("attn", "cummean", "attn", "cummean"))
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params["layers"]) == {"attn", "mha", "cummean", "ln1", "ln2",
                                     "mlp"}
    assert params["layers"]["cummean"]["scale"].shape == (2, 1)
    assert params["layers"]["mha"]["wq"].shape[0] == 2
    assert params["layers"]["attn"]["wo"].shape[0] == 4
    specs = models.partition_specs(cfg)
    assert specs["layers"]["cummean"] == {"scale": P(None, None)}
    assert jax.tree.structure(
        specs, is_leaf=lambda s: s is None or isinstance(s, P)
    ) == jax.tree.structure(jax.tree.map(lambda a: None, params),
                            is_leaf=lambda s: s is None)
    rows = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                              cfg.vocab_size)

    # every layer's own value, as the scan stacked them: a layer of another
    # kind reads zero
    _, aux = jax.jit(lambda p, t: models.forward(
        p, t, cfg, return_aux=True))(params, rows[:, :-1])
    per_layer = np.asarray(aux["layers"][PEAK.key]).reshape(-1)
    assert per_layer.shape == (4,)
    assert (per_layer[[0, 2]] == 0).all() and (per_layer[[1, 3]] > 0).all()
    assert float(aux[PEAK.metric]) == per_layer.max()
    text = jax.jit(lambda p, t: models.forward(p, t, cfg)).lower(
        params, rows[:, :-1]).as_text(debug_info=True)
    assert "attn/attn_linear/attn_core" in text

    opt = optax.adamw(1e-2)
    step = jax.jit(models.make_train_step(cfg, opt))
    state = sm.train_state(params, opt)
    losses = []
    for _ in range(2):
        state, metrics = step(state, {"tokens": rows})
        assert float(metrics[PEAK.metric]) > 0
        losses.append(float(metrics["loss"]))
    assert losses[1] < losses[0] and math.isfinite(losses[1])
    moved = state["params"]["layers"]["cummean"]["scale"] - 1.0
    assert float(jnp.abs(moved).min()) > 0


def test_a_name_no_record_has_is_still_refused():
    with pytest.raises(ValueError, match="names other than "
                                         r"\('attn', 'kda', 'gdn', 'ssm', "
                                         r"'ssm1', 'gmu', 'cross', 'ffn'\)"):
        transformer._check_config(models.tiny(
            arch="llama", layer_mixers=("attn", "cummean")))


# -- (c) the parameters a seed gives --------------------------------------------------

# sha-1 over the leaves' bytes (``jax.tree.leaves`` order) of
# ``init_params(PRNGKey(0), cfg)``, op by op on the CPU, from PR 59's tree
PARENTS = {
    "olmoe": "ce6a5329e4beef16f46a39eeafc103d1b047aed9",
    "smallthinker": "dbdbd647ce34c5831f624cc1b686c86e29f4dc84",
    "kanana2": "0bcd6ae281ef7ac6998974a2dc4b66c323f71bb8",
    "kimi_linear": "a94b08e81f74afd27398007513dcdd28d8ab957b",
    "trinity_mini": "a2bbded21368368a3fc254ab08c9b2c2093c35b1",
    "qwen3_next": "c83388e55ff01de2bcb2d9e3f630e972ca15ee29",
    "nemotron_h": "c768c0e919302159d3100117ebf0cd7646198a5f",
    "tiny": "15559006a218a9bb8a2699d493e8162e72eb4249",
    "tiny_moe": "aa92f46c501c63d962c79ba8ec98fb94b1976ad5",
    "gpt2_small": "33a7d03f4dfeb2fbcc8f04e9d4383b3ea24d0f75",
}


@pytest.mark.parametrize("name", list(PARENTS))
def test_a_seed_gives_the_bytes_it_gave_before_the_seam(name):
    params = models.init_params(jax.random.PRNGKey(0), SMALL[name]())
    digest = hashlib.sha1()
    for leaf in jax.tree.leaves(params):
        digest.update(np.asarray(leaf).tobytes())
    assert digest.hexdigest() == PARENTS[name]


# -- (d) a counter reaches the metrics under its name ---------------------------------

@functools.cache
def _metrics_of(name):
    """The names and shapes of ``lm_loss``'s metrics (traced, not run)."""
    cfg = SMALL[name]()
    rows = jax.ShapeDtypeStruct((2, min(cfg.max_seq_len, 32) + 1), jnp.int32)
    return cfg, jax.eval_shape(
        lambda p, r: models.lm_loss(p, {"tokens": r}, cfg)[1], cfg.shapes(),
        rows)


@pytest.mark.parametrize("metric,key,name", [
    ("router_aux", "balance", "olmoe"),
    ("router_z", "z", "olmoe"),
    ("moe_load_max", "load_max", "olmoe"),
    ("moe_held_share", "held_share", "smallthinker"),
    ("moe_full_buffer", "full_buffer", "smallthinker"),
    ("moe_expert_counts", "counts", "kanana2"),
    ("moe_bias_swapped", "bias_swapped", "kanana2"),
    ("moe_shared_gate_mean", "shared_gate_mean", "qwen3_next"),
    ("attn_gate_mean", "gate_mean", "trinity_mini"),
    ("kda_log_decay_min", "log_decay_min", "kimi_linear"),
    ("ssm_step_mean", "step_mean", "nemotron_h"),
])
def test_a_counter_reaches_the_metrics_under_its_name(metric, key, name):
    cfg, metrics = _metrics_of(name)
    table = {k.metric: k for k in (*transformer.EXPERT_COUNTERS,
                                   mixers.GATE_MEAN, mixers.LOG_DECAY_MIN,
                                   mixers.STEP_MEAN)}
    assert len(table) == 11 and table[metric].key == key
    counters = transformer._counters(cfg)
    assert table[metric] in counters
    expert_layers = len(counters[table[metric]])
    want = (expert_layers, cfg.n_experts) if key == "counts" else ()
    assert metrics[metric].shape == want
    # ... and nothing the tables do not name, beside the loss's own three
    assert set(metrics) == {k.metric for k in counters} | {
        "loss", "accuracy", "perplexity"}


def test_a_dense_model_of_plain_attention_reports_no_counter():
    cfg, metrics = _metrics_of("tiny")
    assert not transformer._counters(cfg)
    assert set(metrics) == {"loss", "accuracy", "perplexity"}
    cfg = replace(cfg, arch="llama", attn_gate=True)
    assert list(transformer._counters(cfg)) == [mixers.GATE_MEAN]
