"""``tests/_small_models.py``: a shared compile can never stand in for a
configuration it was not built for (the key is the whole configuration),
cases that differ in the seed alone do share one, and ``make`` scales what
it says it scales."""

from __future__ import annotations

import dataclasses
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models

import _small_models as sm


def small(**kw):
    return models.tiny_moe(**{"dtype": "float32", **kw})


def test_every_field_of_a_configuration_is_in_its_hash():
    """``functools.cache`` keys on ``hash`` and ``==``: a field left out
    of either would let two configurations share a compile."""
    cls = models.TransformerConfig
    assert cls.__dataclass_params__.frozen and cls.__dataclass_params__.eq
    assert all(f.compare and f.hash is not False
               for f in dataclasses.fields(cls))


# one field off the same small model, a field a kind of value
ONE_FIELD = [dict(n_layers=3), dict(remat=False), dict(remat_policy="dots"),
             dict(scan_layers=False), dict(expert_top_k=1),
             dict(router_aux_weight=0.02), dict(norm_eps=1e-6),
             dict(experts_held=(0, 2)), dict(dtype="bfloat16")]


@pytest.mark.parametrize("get", [
    lambda cfg: sm.jitted(models.forward, cfg),
    lambda cfg: sm.value_and_grad(sm.program_loss, cfg),
    lambda cfg: sm.value_and_grad(sm._lm_loss, cfg, has_aux=True),
    lambda cfg: sm.train_step(cfg, sm.adamw(3e-4)),
], ids=["jitted", "value_and_grad", "has_aux", "train_step"])
def test_an_equal_configuration_is_the_same_callable_and_one_field_off_another(
        get):
    cfg, again = small(), small()
    assert cfg == again and cfg is not again
    assert get(cfg) is get(again)
    others = [get(replace(cfg, **change)) for change in ONE_FIELD]
    assert len({id(f) for f in others} | {id(get(cfg))}) == len(ONE_FIELD) + 1
    assert all(replace(cfg, **change) != cfg for change in ONE_FIELD)


def test_another_function_optimizer_or_accumulation_is_another_callable():
    cfg = small()
    assert sm.jitted(models.forward, cfg) is not sm.jitted(sm._lm_loss, cfg)
    assert sm.adamw(3e-4) is sm.adamw(3e-4)
    assert sm.adamw(3e-4) is not sm.adamw(3e-4, weight_decay=0.1)
    step = sm.train_step(cfg, sm.adamw(3e-4))
    assert step is not sm.train_step(cfg, sm.adamw(1e-3))
    assert step is not sm.train_step(cfg, sm.adamw(3e-4), accum_steps=2)
    # a default left out and the same value given are one key
    assert step is sm.train_step(cfg, sm.adamw(3e-4, 1e-4), accum_steps=1)
    assert sm.value_and_grad(sm.program_loss, cfg) is sm.value_and_grad(
        sm.program_loss, cfg, has_aux=False)


def test_seeds_share_one_executable_and_a_precision_context_has_its_own():
    cfg, params, rows = sm.make(small, 0, tokens=16)
    _, other, other_rows = sm.make(small, 1, tokens=16)
    compiled = sm.jitted(models.forward, cfg)
    before = compiled._cache_size()
    a = sm.forward(params, rows[:, :-1], cfg)
    b = sm.forward(other, other_rows[:, :-1], cfg)
    assert compiled._cache_size() == before + 1
    assert float(jnp.abs(a - b).max()) > 0
    with jax.default_matmul_precision("highest"):
        sm.forward(params, rows[:, :-1], cfg)
    assert compiled._cache_size() == before + 2
    # and what the shared compiles give is the program's own, op by op
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(models.forward(params, rows[:, :-1], cfg)),
        rtol=1e-5, atol=1e-6)
    (loss, metrics), grads = sm.loss_metrics_and_grads(params, rows, cfg)
    want, want_metrics = sm.lm_loss(params, rows, cfg)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert set(metrics) == set(want_metrics) and "router_aux" in metrics
    assert jax.tree.structure(grads) == jax.tree.structure(params)


def test_make_scales_the_stacks_but_what_stays_as_drawn():
    cfg, params, rows = sm.make(small, 3, tokens=16)
    drawn = models.init_params(jax.random.PRNGKey(3), cfg)
    assert rows.shape == (2, 17) and int(rows.max()) < cfg.vocab_size
    layers, was = params["layers"], drawn["layers"]
    for name in ("ln1", "ln2"):
        assert bool(jnp.array_equal(layers[name]["w"], was[name]["w"]))
    assert bool(jnp.array_equal(layers["attn"]["wq"],
                                was["attn"]["wq"] * sm.SCALE))
    assert bool(jnp.array_equal(
        layers["router"]["w"],
        was["router"]["w"] * sm.SCALE * sm.ROUTER_SCALE))
    assert bool(jnp.array_equal(params["embed"]["tokens"],
                                drawn["embed"]["tokens"]))
    # the arguments of ``scaled`` are taken out of the configuration's
    other, params, _ = sm.make(small, 3, tokens=16, scale=2.0,
                               router_scale=1.0, as_drawn=("ln1", "ln2", "wq"))
    assert other == cfg
    assert bool(jnp.array_equal(params["layers"]["attn"]["wq"],
                                was["attn"]["wq"]))
    assert bool(jnp.array_equal(params["layers"]["router"]["w"],
                                was["router"]["w"] * 2.0))
    assert sm.make(small, 3)[2].shape == (2, cfg.max_seq_len + 1)
    assert sm.make(small, 3, n_layers=3)[0].n_layers == 3
