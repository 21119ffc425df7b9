"""OLMoE on the normal path against its plain reference
(``chipbench/reference/olmoe.py``), at an OLMoE-shaped small size on the
CPU: 2 layers, hidden 64, 4 heads, 8 experts of width 32, top 3, dropless,
gates not renormalised, QK-norm over the full width, balance + z losses.

Weights: the program's own N(0, 0.02) init makes a 64-wide model's
branches vanish beside the residual stream, so the layer weights are
drawn at ``SCALE`` x that (0.1: the experts' branch then moves the logits
by ~0.1 of their spread, see ``test_the_expert_branch_moves_the_logits``)
and the router at 10 x that again, so that routing is uneven (the gates
spread from 0.02 to 0.6) and renormalising them is a visible change.
The q / k norm weights are drawn around 1 so that "per head" and "full
width" differ in more than a constant.

Both sides compute in float32 here: only the order of operations
differs, so the tolerances are float32 rounding (2^-24 relative) grown by
the depth of the sums: 2e-5 on logits and the loss, 1e-5 + 1e-3 relative
on a gradient. A broken variant has to miss by 100 x that.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmoe as reference
from ray_tpu import models
from ray_tpu.models import transformer
from ray_tpu.ops import moe

SCALE = 5.0
TOL = 2e-5


def small(**kw):
    return models.olmoe_1b_7b(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32,
        n_experts=8, expert_top_k=3, vocab_size=256, max_seq_len=64,
        dtype="float32", **kw)


def make(seed: int = 0, skew: float = 0.0):
    """(cfg, params, rows [2, 33]). ``skew`` gives every token's hidden
    state a common direction (a constant added to the embedding) and
    points expert 0's router column along it: about ``skew`` x 55 on its
    logit, whose spread is 8, so most tokens choose expert 0."""
    cfg = small()
    params = models.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1000), 8))
    layers = jax.tree.map(lambda a: a * SCALE, params["layers"])
    layers["router"]["w"] = layers["router"]["w"] * 10.0
    for name in ("ln1", "ln2"):
        layers[name]["w"] = params["layers"][name]["w"]
    for name in ("q_norm", "k_norm"):
        layers["attn"][name] = 1.0 + 0.5 * jax.random.normal(
            next(keys), params["layers"]["attn"][name].shape)
    params = dict(params, layers=layers)
    if skew:
        layers["router"]["w"] = layers["router"]["w"].at[:, :, 0].add(skew)
        params["embed"] = {"tokens": params["embed"]["tokens"] + 0.05}
    rows = jax.random.randint(next(keys), (2, 33), 0, cfg.vocab_size)
    return cfg, params, rows


def program_loss(params, rows, cfg):
    return models.lm_loss(params, {"tokens": rows}, cfg)[0]


def test_preset_is_olmoe_as_published():
    c = models.olmoe_1b_7b()
    assert (c.vocab_size, c.n_layers, c.d_model, c.n_heads, c.kv_heads,
            c.head_dim, c.ffn_dim, c.n_experts, c.expert_top_k,
            c.max_seq_len, c.arch, c.tied) == (
        50304, 16, 2048, 16, 16, 128, 1024, 64, 8, 4096, "llama", False)
    assert c.expert_capacity_factor is None and not c.expert_norm_topk
    assert c.qk_norm and (c.router_aux_weight, c.router_z_weight) == (
        0.01, 0.001)
    d = models.TransformerConfig()      # the step's options are defaults
    assert (c.attn_impl, c.remat, c.remat_policy, c.loss_chunk) == (
        d.attn_impl, d.remat, d.remat_policy, d.loss_chunk)
    assert models.olmoe_1b_7b(n_layers=1).num_params() == 625_616_896


def test_the_expert_branch_moves_the_logits():
    """The scale of this file's weights: zeroing the experts' output
    changes the logits by a tenth of their spread, so a fault in the
    expert branch cannot hide under the tolerance."""
    cfg, params, rows = make()
    want = models.forward(params, rows[:, :-1], cfg)
    mlp = dict(params["layers"]["mlp"],
               w_down=params["layers"]["mlp"]["w_down"] * 0.0)
    off = models.forward(dict(params, layers=dict(params["layers"], mlp=mlp)),
                         rows[:, :-1], cfg)
    moved = float(jnp.abs(want - off).max())
    assert moved > 0.05 * float(want.std()) and moved > 1000 * TOL


@pytest.mark.parametrize("seed", [0, 1])
def test_program_equals_reference_logits_loss_and_gradients(seed):
    cfg, params, rows = make(seed)
    got = models.forward(params, rows[:, :-1], cfg)
    want = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(got - want).max()) < TOL
    loss, metrics = models.lm_loss(params, {"tokens": rows}, cfg)
    assert float(loss) == pytest.approx(
        float(reference.loss(params, rows, cfg)), abs=TOL)
    # the whole loss is cross entropy + 0.01 x balance + 0.001 x z
    rest = 0.01 * float(metrics["router_aux"]) + 0.001 * float(
        metrics["router_z"])
    assert rest > 0.012 and float(loss) - rest == pytest.approx(
        float(metrics["loss"]) - rest)
    # gradients: the experts' and the router's weights, and a q norm
    g = jax.grad(program_loss)(params, rows, cfg)["layers"]
    r = jax.grad(reference.loss)(params, rows, cfg)["layers"]
    for got_g, want_g in ((g["mlp"]["w_gate"][0, 2], r["mlp"]["w_gate"][0, 2]),
                          (g["mlp"]["w_down"][1, 5], r["mlp"]["w_down"][1, 5]),
                          (g["router"]["w"], r["router"]["w"]),
                          (g["attn"]["q_norm"], r["attn"]["q_norm"])):
        assert float(jnp.abs(want_g).max()) > 1e-4      # not a dead branch
        np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                                   rtol=1e-3, atol=1e-5)


def _per_head_qk_norm(x, weight, *, eps=1e-5):
    """The wrong QK-norm: over each head's 16 values, not all 64."""
    xf = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return xf * weight.reshape(x.shape[-2:])


def _balance_over_token_shares(real):
    def dropless(x, router_w, *weights, top_k, **kw):
        out, stats = real(x, router_w, *weights, top_k=top_k, **kw)
        # The capacity path's term: over a group of at most 1024 tokens
        # (these 64 are one group), f the share of TOKENS that chose e,
        # which sums to top_k: top_k x the recipe's term.
        return out, dict(stats, balance=stats["balance"] * top_k)
    return dropless


# what is broken -> (config changes, router skew, what to patch, what misses)
BROKEN = {
    "gates renormalised": (dict(expert_norm_topk=True), 0.0, None, "logits"),
    "QK-norm per head, not full width": ({}, 0.0, "qk_norm", "logits"),
    "z term left out": (dict(router_z_weight=0.0), 0.0, None, "loss"),
    "balance over groups with f summing to top_k":
        ({}, 0.0, "balance", "loss"),
    "capacity path at factor 1.25 under a skewed router (drops)":
        (dict(expert_capacity_factor=1.25), 0.5, None, "logits"),
}


@pytest.mark.parametrize("name", list(BROKEN))
def test_a_broken_variant_fails_the_comparison(name, monkeypatch):
    """Each case breaks one thing in the program; the comparison of
    ``test_program_equals_reference...`` (logits and the whole loss within
    ``TOL``) has to fail by a wide margin, or that test proves nothing
    about the thing."""
    changes, skew, patch, what = BROKEN[name]
    cfg, params, rows = make(skew=skew)
    want = {"logits": reference.forward(params, rows[:, :-1], cfg),
            "loss": reference.loss(params, rows, cfg)}
    if patch == "qk_norm":
        monkeypatch.setattr(transformer, "_qk_norm", _per_head_qk_norm)
    if patch == "balance":
        monkeypatch.setattr(moe, "moe_swiglu_dropless",
                            _balance_over_token_shares(
                                moe.moe_swiglu_dropless))
    cfg = replace(cfg, **changes)
    got = {"logits": models.forward(params, rows[:, :-1], cfg),
           "loss": program_loss(params, rows, cfg)}
    miss = float(jnp.abs(got[what] - want[what]).max())
    assert miss > 100 * TOL, (name, miss)


def test_dropless_computes_every_assignment_under_skew():
    """A router that sends over half of the assignments to one expert:
    the dropless path still equals the reference (which has no capacity
    at all), and ``moe_load_max`` equals a count made with numpy."""
    cfg, params, rows = make(skew=0.5)
    got = models.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(got - reference.forward(params, rows[:, :-1], cfg)
                         ).max()) < TOL
    # layer 0's router on the program's own normed hidden states
    cfg1 = replace(cfg, n_layers=1)
    first = jax.tree.map(lambda a: a[:1], params["layers"])
    p1 = dict(params, layers=first)
    _, metrics = models.lm_loss(p1, {"tokens": rows}, cfg1)
    h = _router_inputs(p1, rows[:, :-1], cfg1)
    logits = np.asarray(h, np.float64) @ np.asarray(first["router"]["w"][0],
                                                    np.float64)
    chosen = np.argsort(-logits, axis=-1)[:, :cfg.expert_top_k]
    counts = np.bincount(chosen.reshape(-1), minlength=cfg.n_experts)
    # over half of the 64 tokens choose expert 0: more than the 32 slots
    # the capacity path would give it at factor 1.25
    assert counts[0] > 32 == moe.expert_capacity(64, 8, 3, 1.25)
    assert counts.max() / counts.mean() == pytest.approx(
        float(metrics["moe_load_max"]), rel=1e-6)
    assert counts.max() / counts.mean() > 2.0


def _router_inputs(params, tokens, cfg):
    """u = RMSNorm(h) of layer 0, [N, D], by the reference's pieces."""
    from chipbench.reference import _common
    from chipbench.reference.llama import _rms

    lp = _common.layer_slice(params["layers"], 0)
    x = params["embed"]["tokens"][tokens].astype(jnp.float32)
    h = reference._attention(x, lp, cfg.n_heads, float(cfg.rope_theta))
    return _rms(h, lp["ln2"]["w"]).reshape(-1, x.shape[-1])


def test_dropless_on_an_expert_mesh_is_refused_by_name():
    from ray_tpu.parallel.mesh import MeshConfig

    cfg, params, rows = make()
    mesh = MeshConfig(data=2, expert=4).build()
    with pytest.raises(NotImplementedError, match="ROADMAP B3"):
        models.forward(params, rows[:, :-1], cfg, mesh=mesh)
    # a mesh whose expert axis is 1 (the benchmark's) is fine
    flat = MeshConfig(data=1, fsdp=-1).build(jax.devices()[:1])
    models.forward(params, rows[:, :-1], cfg, mesh=flat)


def test_grouped_matmul_and_the_row_moves_by_hand():
    """``grouped_matmul`` is each run of rows times its own matrix, and
    the two row moves are each other's transposes (their custom gradients
    equal the gradients jax derives for the plain gathers)."""
    rng = np.random.default_rng(0)
    sizes = np.array([3, 0, 5, 4], np.int32)
    lhs = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 8, 6)), jnp.float32)
    got = moe.grouped_matmul(lhs, rhs, jnp.asarray(sizes))
    start = 0
    for g, n in enumerate(sizes):
        np.testing.assert_allclose(np.asarray(got[start:start + n]),
                                   np.asarray(lhs[start:start + n] @ rhs[g]),
                                   rtol=1e-5, atol=1e-5)
        start += n
    k, x = 3, jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    order = jnp.asarray(rng.permutation(12), jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    w = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    mine = jax.grad(lambda x: (moe._rows_to_experts(x, order, inverse, k)
                               * w).sum())(x)
    plain = jax.grad(lambda x: (x[order // k] * w).sum())(x)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(plain), rtol=1e-6)
    mine = jax.grad(lambda y: (moe._rows_to_tokens(y, order, inverse)
                               * w).sum())(w * 2)
    plain = jax.grad(lambda y: (y[inverse] * w).sum())(w * 2)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(plain), rtol=1e-6)
