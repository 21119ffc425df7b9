"""The plain references against ``models.forward`` and ``models.lm_loss``
at ``tiny``: the two of ``chipbench/reference/`` and the one a later PR
might add (``tests/chipbench/tiny/tinymoe.reference.py``, whose training
loss has a router term)."""

from __future__ import annotations

import functools

import pytest

import _tinycells

TINYMOE = dict(expert_capacity_factor=2.0, router_aux_weight=0.01)


def _reference(arch: str):
    from chipbench import spec

    if arch == "tinymoe":
        return _tinycells.load_tiny("tinymoe.reference.py")
    return spec.load_part("reference", arch)


@pytest.mark.parametrize("arch,factory,kwargs", [
    ("llama", "tiny", {"arch": "llama", "n_kv_heads": 2}),
    ("gpt2", "tiny", {}),
    ("tinymoe", "tiny_moe", TINYMOE),
])
def test_reference_agrees_with_the_program_in_float32(arch, factory, kwargs):
    import jax
    import jax.numpy as jnp

    from chipbench.reference import _common
    from ray_tpu import models

    # float32 compute on both sides: only the order of operations
    # differs, so 1e-5 on logits of magnitude ~1 (float32 has 2^-24).
    cfg = getattr(models, factory)(dtype="float32", **kwargs)
    params = models.init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0,
                              cfg.vocab_size)
    ref = _reference(arch)
    want = models.forward(params, toks[:, :-1], cfg)
    got = ref.forward(params, toks[:, :-1], cfg)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # the WHOLE training loss, as the train cells compare it
    whole = _common.training_loss(ref, params, toks, cfg)
    assert (whole is None) == (arch != "tinymoe")   # who states a loss
    ce = float(_common.next_token_loss(got, toks))
    loss = ce if whole is None else float(whole)
    want_loss, metrics = models.lm_loss(params, {"tokens": toks}, cfg)
    assert loss == pytest.approx(float(want_loss), abs=1e-5)
    rest = cfg.router_aux_weight * float(metrics.get("router_aux", 0.0))
    assert loss - ce == pytest.approx(rest, abs=1e-5)
    assert (rest > 0) == (arch == "tinymoe")


def test_reference_attention_in_blocks_equals_one_block():
    import jax
    import jax.numpy as jnp

    from chipbench.reference import _common

    q, k, v = (jax.random.normal(kk, (1, 48, 2, 8), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    a = _common.causal_attention(q, k, v, block_q=16)
    b = _common.causal_attention(q, k, v, block_q=1024)
    assert float(jnp.abs(a - b).max()) < 1e-6


# --- the comparison that decides a train cell's ``correct`` --------------
# ``_common.agreement`` under ``_common.LIMITS``, at ``tiny``, on every
# reference module with a tiny preset, sound and with each control of
# ``_common.CONTROLS`` (and, with experts, the router term left out of the
# program's loss). CPU, bfloat16 compute against the float32 reference.

def _tiny(arch: str):
    """(cfg, reference module) of ``arch`` at test size."""
    from chipbench import spec
    from ray_tpu import models

    if arch == "gpt2":
        cfg = models.tiny()
    elif arch == "llama":
        cfg = models.tiny(arch="llama", n_kv_heads=2)
    elif arch == "olmoe":
        cfg = models.olmoe_1b_7b(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32,
            n_experts=8, expert_top_k=3, vocab_size=256, max_seq_len=64)
    elif arch == "smallthinker":
        cfg = spec.model_config(spec.load_json(
            "tests", "chipbench", "tiny", "tiny-smallthinker.config.json",
            root=_tinycells.REPO))
    else:
        cfg = models.tiny_moe(**TINYMOE)
    return cfg, _reference(arch)


@functools.lru_cache(maxsize=None)
def _agreement(arch: str, control: str, seed: int) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.drivers import train_job
    from chipbench.reference import _common
    from ray_tpu import models

    cfg, ref = _tiny(arch)
    params = models.init_params(jax.random.PRNGKey(seed), cfg)
    rows = np.asarray(jax.random.randint(
        jax.random.PRNGKey(100 + seed), (2, 33), 0, cfg.vocab_size))
    run_cfg = cfg
    if control == "no_router_term":
        run_cfg, control = dataclasses.replace(
            cfg, router_aux_weight=0.0, router_z_weight=0.0), "sound"
    for_program, for_reference = _common.apply_control(
        None if control == "sound" else control, params)
    call = train_job.program_side(run_cfg)

    def program():
        tokens = jnp.asarray(rows)
        return call(for_program, tokens, tokens)[:2]

    return _common.agreement(ref, for_reference, rows, cfg, program)


def _beyond(stats: dict, factor: float) -> list[str]:
    """The statistics outside their limit by ``factor`` or more (in the
    statistic's own units; ``argmax_agree`` in disagreement)."""
    from chipbench.reference import _common

    out = []
    for name, (op, limit) in _common.LIMITS.items():
        v = stats[name]
        if op == ">=":
            v, limit = 1.0 - v, 1.0 - limit
        if v >= factor * limit:
            out.append(name)
    return out


DENSE = ("sound", "bf16_weights", "drop_layer=1", "float8_weights",
         "scale_layers=4")
SPARSE = DENSE + ("drop_experts=1", "no_router_term")


@pytest.mark.parametrize("arch,control", [
    (a, c) for a, cs in (("gpt2", DENSE), ("llama", DENSE), ("olmoe", SPARSE),
                         ("smallthinker", SPARSE), ("tinymoe", SPARSE))
    for c in cs])
def test_limits_hold_the_sound_program_and_fail_every_control(arch, control):
    """At ``tiny`` every reading is about a tenth of what it is at the
    published widths (PERF.md section 4 has the chip's table), the sound
    program's and a control's alike, so what carries over is how far a
    control stands from the sound program on the same weights and rows."""
    from chipbench.reference import _common

    limit = {k: v for k, (_op, v) in _common.LIMITS.items()}
    for seed in range(2):
        stats = _agreement(arch, control, seed)
        sound = _agreement(arch, "sound", seed)
        shown = {k: stats[k] for k in _common.RECORDED}
        if control in ("sound", "bf16_weights"):
            assert not _common.outside(stats), shown
            assert stats["rest_d"] <= limit["rest_d"] / 10, shown
            # Not a tenth: bfloat16's rounding is relative, 0.0036-0.0045
            # of the logits' spread at any size with two layers; and one
            # of these 64 positions is 1.6% of agreement.
            assert stats["logit_rel_d"] <= 0.15 * limit["logit_rel_d"], shown
            assert stats["argmax_agree"] >= 0.95, shown
            # rounding the reference's weights moves a reading by less
            # than the sound program's whole reading
            assert abs(stats["logit_rel_d"] - sound["logit_rel_d"]) \
                <= 0.3 * sound["logit_rel_d"], shown
        elif control == "no_router_term":
            # the logits are the sound program's; the loss lacks the term
            assert _beyond(stats, 3) == ["rest_d"], shown
            assert stats["logit_rel_d"] == sound["logit_rel_d"]
        elif control.startswith(("drop_layer", "scale_layers")):
            assert "logit_rel_d" in _beyond(stats, 3), shown
        elif control == "float8_weights":
            # 7.4-9.0 x the sound reading on the chip, in every cell; here
            # 7-9 x, which at this size is still inside the limit
            assert stats["logit_rel_d"] >= 6 * sound["logit_rel_d"], shown
        else:
            # One layer's experts out. tinymoe (2 of 4 experts a token, 256
            # wide) reads 14 x the sound program and is outside the limit;
            # the two presets with 32-wide experts read 1.6-2.1 x: their
            # experts add that little to a 64-wide stream. On the chip:
            # 8.5 x at the least (PERF.md section 4).
            assert control.startswith("drop_experts")
            assert stats["logit_rel_d"] >= 1.5 * sound["logit_rel_d"], shown
            if arch == "tinymoe":
                assert "logit_rel_d" in _common.outside(stats), shown


def test_a_control_names_its_side_and_unknown_names_are_refused():
    import jax

    from chipbench.reference import _common
    from ray_tpu import models

    cfg = models.tiny()
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    assert _common.apply_control(None, params) == (params, params)
    for name, (side, _fn) in _common.CONTROLS.items():
        assert side == ("reference" if name == "bf16_weights" else "program")
    for_program, for_reference = _common.apply_control("drop_layer=1", params)
    assert for_reference is params
    wo = for_program["layers"]["attn"]["wo"]
    assert float(abs(wo[1]).max()) == 0.0 and float(abs(wo[0]).max()) > 0.0
    assert float(abs(for_program["layers"]["mlp"]["b_out"][1]).max()) == 0.0
    with pytest.raises(ValueError, match="no control 'drop_head'"):
        _common.apply_control("drop_head=1", params)
    with pytest.raises(ValueError, match="no expert matrices"):
        _common.apply_control("drop_experts=0", params)     # a dense model


OWN = {"limit": 0.09, "sound_worst": 0.0394, "nearest_control": 0.258}


def test_a_configuration_file_may_state_its_own_limit_with_its_readings():
    from chipbench.reference import _common

    assert _common.limits_for(None) == _common.LIMITS == _common.limits_for({})
    own = _common.limits_for({"logit_rel_d": OWN, "why": "measured"})
    assert own["logit_rel_d"] == ("<=", 0.09)
    assert own["rest_d"] == _common.LIMITS["rest_d"]
    stats = {"logit_rel_d": 0.04, "argmax_agree": 0.95, "target_logp_d": 0.01,
             "rest_d": 0.0}
    assert _common.outside(stats) == ["logit_rel_d"]
    assert _common.outside(stats, own) == []
    assert _common.outside(dict(stats, rest_d=float("nan")), own) == ["rest_d"]
    assert _common.compared(stats, own)[0] == (
        "compared logit_rel_d 0.04 (limit <= 0.09): ok")


@pytest.mark.parametrize("stated,refused", [
    # a statistic that is recorded and held in no cell; one that is no
    # statistic; the router terms' limit, which is arithmetic and no file's
    ({"argmax_agree": OWN, "why": "w"}, "agreement_limits has"),
    ({"logit_d": OWN, "why": "w"}, "agreement_limits has"),
    ({"rest_d": OWN, "why": "w"}, "agreement_limits has"),
    # a limit with no why, a bare number, a reading left out
    ({"logit_rel_d": OWN}, "no why"),
    ({"logit_rel_d": 0.09, "why": "w"}, "has to give"),
    ({"logit_rel_d": {"limit": 0.09, "sound_worst": 0.0394}, "why": "w"},
     "has to give"),
    # under 2 x the sound program's worst reading; over half way to the
    # nearest control; a control that does not stand clear of the program
    ({"logit_rel_d": dict(OWN, limit=0.07), "why": "w"}, "is not between"),
    ({"logit_rel_d": dict(OWN, limit=0.15), "why": "w"}, "is not between"),
    ({"logit_rel_d": dict(OWN, nearest_control=0.1), "why": "w"},
     "is not between"),
])
def test_a_stated_limit_that_its_readings_do_not_place_is_refused(
        stated, refused):
    from chipbench.reference import _common

    with pytest.raises(ValueError, match=refused):
        _common.limits_for(stated)


def _stated_limits():
    """(file, statistic, its limit and readings) of the module's own
    limits and of every configuration file that states one: whatever a
    later PR adds is picked up by name."""
    import glob
    import json
    import os

    from chipbench.reference import _common

    out = [("_common.READINGS", name, r, "")
           for name, r in _common.READINGS.items()]
    for path in sorted(glob.glob(os.path.join(
            _tinycells.REPO, "chipbench", "configs", "*.json"))):
        with open(path) as f:
            stated = json.load(f).get("agreement_limits") or {}
        out += [(os.path.basename(path), name, r, stated.get("why"))
                for name, r in stated.items() if name != "why"]
    return out


@pytest.mark.parametrize("where,name,placing,why", _stated_limits())
def test_every_limit_is_placed_by_the_rule_from_its_readings(
        where, name, placing, why):
    from chipbench.reference import _common

    limit, sound, control = (placing[k] for k in _common.PLACED_BY)
    assert _common.placed_by_rule(limit, sound, control), (where, placing)
    assert 2 * sound <= limit <= (sound + control) / 2
    # a control under 3 x the sound program is no control: no limit holds
    assert control >= 3 * sound, (where, placing)
    if where == "_common.READINGS":
        assert _common.LIMITS[name] == ("<=", limit)
    else:
        # a file states only what may move with width and depth, says why
        # at length, and is taken as it stands by the driver's ``limits_for``
        assert name in _common.STATED_BY_A_FILE, (where, name)
        assert len(why) > 100, where
        assert _common.limits_for({name: placing, "why": why})[name] == (
            "<=", limit)
