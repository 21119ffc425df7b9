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
from ray_tpu.models import mixers
from ray_tpu.ops import moe

import _small_models as sm

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
    cfg, params, _ = sm.make(small, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1000), 8))
    layers = params["layers"]
    for name in ("q_norm", "k_norm"):
        layers["attn"][name] = 1.0 + 0.5 * jax.random.normal(
            next(keys), layers["attn"][name].shape)
    if skew:
        layers["router"]["w"] = layers["router"]["w"].at[:, :, 0].add(skew)
        params["embed"] = {"tokens": params["embed"]["tokens"] + 0.05}
    rows = jax.random.randint(next(keys), (2, 33), 0, cfg.vocab_size)
    return cfg, params, rows


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
    want = sm.forward(params, rows[:, :-1], cfg)
    mlp = dict(params["layers"]["mlp"],
               w_down=params["layers"]["mlp"]["w_down"] * 0.0)
    off = sm.forward(dict(params, layers=dict(params["layers"], mlp=mlp)),
                     rows[:, :-1], cfg)
    moved = float(jnp.abs(want - off).max())
    assert moved > 0.05 * float(want.std()) and moved > 1000 * TOL


@pytest.mark.parametrize("seed", [0, 1])
def test_program_equals_reference_logits_loss_and_gradients(seed):
    cfg, params, rows = make(seed)
    got = sm.forward(params, rows[:, :-1], cfg)
    want = reference.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(got - want).max()) < TOL
    (loss, metrics), g = sm.loss_metrics_and_grads(params, rows, cfg)
    reference_loss, r = sm.value_and_grad(reference.loss, cfg)(params, rows)
    assert float(loss) == pytest.approx(float(reference_loss), abs=TOL)
    # the whole loss is cross entropy + 0.01 x balance + 0.001 x z
    rest = 0.01 * float(metrics["router_aux"]) + 0.001 * float(
        metrics["router_z"])
    assert rest > 0.012 and float(loss) - rest == pytest.approx(
        float(metrics["loss"]) - rest)
    # gradients: the experts' and the router's weights, and a q norm
    g, r = g["layers"], r["layers"]
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
        monkeypatch.setattr(mixers, "_qk_norm", _per_head_qk_norm)
    if patch == "balance":
        monkeypatch.setattr(moe, "moe_swiglu_dropless",
                            _balance_over_token_shares(
                                moe.moe_swiglu_dropless))
    cfg = replace(cfg, **changes)
    if patch:       # a patched program is in no key of ``sm``: op by op
        got = {"logits": models.forward(params, rows[:, :-1], cfg),
               "loss": sm.program_loss(params, rows, cfg)}
    else:
        got = {"logits": sm.forward(params, rows[:, :-1], cfg),
               "loss": sm.loss(params, rows, cfg)}
    miss = float(jnp.abs(got[what] - want[what]).max())
    assert miss > 100 * TOL, (name, miss)


def test_dropless_computes_every_assignment_under_skew():
    """A router that sends over half of the assignments to one expert:
    the dropless path still equals the reference (which has no capacity
    at all), and ``moe_load_max`` equals a count made with numpy."""
    cfg, params, rows = make(skew=0.5)
    got = sm.forward(params, rows[:, :-1], cfg)
    assert float(jnp.abs(got - reference.forward(params, rows[:, :-1], cfg)
                         ).max()) < TOL
    # layer 0's router on the program's own normed hidden states
    cfg1 = replace(cfg, n_layers=1)
    first = jax.tree.map(lambda a: a[:1], params["layers"])
    p1 = dict(params, layers=first)
    _, metrics = sm.lm_loss(p1, rows, cfg1)
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
    """``grouped_matmul`` is each run of rows times its own matrix, its
    two gradients written out (``_grouped_matmul_grads``) are the ones jax
    derives for ``ragged_dot``, and the dispatch's row move has the
    gradient jax derives for the plain gather."""
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
    cot = jnp.asarray(rng.normal(size=(12, 6)), jnp.float32)
    mine = moe._grouped_matmul_grads(lhs, rhs, jnp.asarray(sizes), cot)
    plain = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, jnp.asarray(sizes)),
                    lhs, rhs)[1](cot)
    for a, b in zip(mine, plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# -- the block's own backward (``moe._down_and_combine``) ---------------------

BLOCK_E, BLOCK_D, BLOCK_F, BLOCK_B, BLOCK_S = 16, 24, 8, 2, 64
# router column 0's weight on the tokens' common direction (x[..., 0] = 1)
ROUTINGS = {"balanced": 0.0, "one expert empty": -50.0,
            "one expert chosen by over half of the tokens": 3.0}


def block_inputs(routing: str, dtype=jnp.float32, seed: int = 0):
    """(x [B, S, D], router_w, w_gate, w_up, w_down) of a small block.
    Every token carries 1.0 in its first feature and expert 0's router
    column weighs it by ``ROUTINGS[routing]``."""
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    x = draw(BLOCK_B, BLOCK_S, BLOCK_D).at[:, :, 0].set(1.0)
    router_w = draw(BLOCK_D, BLOCK_E).at[0].set(0.0)
    router_w = router_w / jnp.linalg.norm(router_w, axis=0)   # even columns
    router_w = router_w.at[0, 0].set(ROUTINGS[routing])
    weights = (draw(BLOCK_E, BLOCK_D, BLOCK_F, scale=0.3),
               draw(BLOCK_E, BLOCK_D, BLOCK_F, scale=0.3),
               draw(BLOCK_E, BLOCK_F, BLOCK_D, scale=0.3))
    return (x.astype(dtype), router_w, *(w.astype(dtype) for w in weights))


def plain_block(x, router_w, w_gate, w_up, w_down, *, top_k, norm_topk):
    """The block with no sort, no gather and no custom gradient: every
    expert on every token, weighted by a dense [N, E] matrix of gates.
    Returns what ``moe_swiglu_dropless`` does, less ``load_max``."""
    xf = x.reshape(-1, x.shape[-1])
    logits = xf @ router_w
    probs, gates, experts = moe.route(logits, top_k, norm_topk)
    n_experts = router_w.shape[-1]
    weight = (jax.nn.one_hot(experts, n_experts) * gates[:, :, None]).sum(1)
    h = jax.nn.silu(jnp.einsum("nd,edf->nef", xf, w_gate)) * jnp.einsum(
        "nd,edf->nef", xf, w_up)
    out = jnp.einsum("ne,nef,efd->nd", weight, h, w_down)
    share = jax.nn.one_hot(experts, n_experts).sum((0, 1)) / experts.size
    return out.reshape(x.shape), {
        "balance": n_experts * (share * probs.mean(0)).sum(),
        "z": moe.router_z(logits)}


def _close(got, want, rel: float, name=""):
    """|got - want| <= rel x (|want| + the largest |want|)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, name
    assert np.abs(want).max() > 1e-4, name               # not a dead branch
    np.testing.assert_allclose(got, want, rtol=rel,
                               atol=rel * np.abs(want).max(), err_msg=name)


@pytest.mark.parametrize("routing", list(ROUTINGS))
@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize("top_k", [1, 3, 8])
def test_the_blocks_own_backward_equals_jaxs(top_k, norm_topk, routing):
    """``_down_and_combine``'s custom gradient, for every input it
    differentiates (``h``, ``w_down``, the gates), equals ``jax.grad`` of
    the plain composition (``ragged_dot``, ``y[inverse]``, the weighted
    sum; no custom rule), and ``jax.grad`` of the whole
    ``moe_swiglu_dropless`` equals that of ``plain_block`` for ``x``,
    ``router_w`` and the three expert weights. Float32 on both sides, so
    only the order of the sums differs: ``rtol`` 1e-5, and 1e-5 of the
    largest entry for the entries near zero. (With ``top_k`` distinct
    choices a token, one expert holds at most 1 / ``top_k`` of the
    assignments: "over half" is of the tokens.)"""
    x, router_w, w_gate, w_up, w_down = block_inputs(routing)
    n = BLOCK_B * BLOCK_S
    _, gates, experts = moe.route(x.reshape(n, -1) @ router_w, top_k,
                                  norm_topk)
    chosen = np.bincount(np.asarray(experts).reshape(-1), minlength=BLOCK_E)
    mean = n * top_k / BLOCK_E
    assert {"balanced": chosen.min() > 0 and chosen.max() < 3 * mean,
            "one expert empty": chosen[0] == 0,
            "one expert chosen by over half of the tokens":
                chosen[0] > max(n // 2, 1.5 * mean)}[routing], chosen
    order = np.argsort(np.asarray(experts).reshape(-1), kind="stable")
    inverse = jnp.asarray(np.argsort(order), jnp.int32)
    order = jnp.asarray(order, jnp.int32)
    counts = jnp.asarray(chosen, jnp.int32)
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(n * top_k, BLOCK_F)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(n, BLOCK_D)), jnp.float32)

    def mine(h, w_down, gates):
        return (moe._down_and_combine(h, w_down, gates, counts, order,
                                      inverse) * cot).sum()

    def plain(h, w_down, gates):
        y = jax.lax.ragged_dot(h, w_down, counts)
        rows = y[inverse].reshape(n, top_k, BLOCK_D)
        return ((rows * gates[:, :, None]).sum(1) * cot).sum()

    assert float(mine(h, w_down, gates)) == pytest.approx(
        float(plain(h, w_down, gates)), rel=1e-5)
    for name, a, b in zip(("h", "w_down", "gates"),
                          jax.grad(mine, (0, 1, 2))(h, w_down, gates),
                          jax.grad(plain, (0, 1, 2))(h, w_down, gates)):
        _close(a, b, 1e-5, name)

    cot = cot.reshape(x.shape)

    def loss(block):
        def f(*args):
            out, stats = block(*args, top_k=top_k, norm_topk=norm_topk)
            return (out * cot).sum() + stats["balance"] + stats["z"]
        return f

    args = (x, router_w, w_gate, w_up, w_down)
    for name, a, b in zip(
            ("x", "router_w", "w_gate", "w_up", "w_down"),
            jax.grad(loss(moe.moe_swiglu_dropless), range(5))(*args),
            jax.grad(loss(plain_block), range(5))(*args)):
        _close(a, b, 1e-5, name)


def test_the_bfloat16_block_stays_with_the_float32_one():
    """The block and its gradients at bfloat16 rows and expert weights
    (the configuration's precision) against the same block in float32 on
    the same values (rounded to bfloat16 first: one flipped choice of the
    router would be a step, not a rounding). The float32 tolerance of
    ``test_program_equals_reference...`` cannot hold at an 8-bit mantissa
    on either rule, so this is the kernels' bfloat16 tolerance
    (``tests/test_ops_parallel.py``): 2^-6 of the largest entry. And the
    rule written here rounds no worse than jax's own transpose of the
    plain composition does at bfloat16 (it only moves WHERE the rounding
    falls: from ``y`` to the down matmul's row gradient)."""
    top_k = 3
    full = tuple(a.astype(jnp.bfloat16).astype(jnp.float32)
                 for a in block_inputs("balanced"))
    cot = jnp.asarray(np.random.default_rng(1).normal(size=full[0].shape),
                      jnp.float32)

    def grads(args, dtype):
        x, router_w, *weights = args
        args = (x.astype(dtype), router_w, *(w.astype(dtype) for w in weights))
        return jax.grad(lambda *a: (moe.moe_swiglu_dropless(
            *a, top_k=top_k, norm_topk=False)[0].astype(jnp.float32)
            * cot).sum(), range(5))(*args)

    want = grads(full, jnp.float32)
    got = grads(full, jnp.bfloat16)
    for name, a, b in zip(("x", "router_w", "w_gate", "w_up", "w_down"),
                          got, want):
        assert a.dtype == (jnp.float32 if name == "router_w"
                           else jnp.bfloat16), name
        worst = float(np.abs(np.asarray(a, np.float32) - np.asarray(b)).max())
        assert worst <= 2.0 ** -6 * float(np.abs(np.asarray(b)).max()), (
            name, worst)

    # the rule alone against jax's transpose of the plain composition,
    # both at bfloat16, both measured against float32
    n, a_rows = BLOCK_B * BLOCK_S, BLOCK_B * BLOCK_S * top_k
    rng = np.random.default_rng(2)
    experts = rng.integers(0, BLOCK_E, size=a_rows)
    order = np.argsort(experts, kind="stable")
    inverse = jnp.asarray(np.argsort(order), jnp.int32)
    order = jnp.asarray(order, jnp.int32)
    counts = jnp.asarray(np.bincount(experts, minlength=BLOCK_E), jnp.int32)
    h = jnp.asarray(rng.normal(size=(a_rows, BLOCK_F)), jnp.float32)
    gates = jnp.asarray(rng.uniform(0.02, 0.6, size=(n, top_k)), jnp.float32)
    cot = cot.reshape(n, BLOCK_D)

    def mine(h, w, gates):
        return moe._down_and_combine(h, w, gates, counts, order, inverse)

    def plain(h, w, gates):
        rows = jax.lax.ragged_dot(h, w, counts)[inverse].reshape(n, top_k, -1)
        return (rows.astype(jnp.float32) * gates[:, :, None]).sum(1).astype(
            h.dtype)

    def miss(f, dtype):
        out = jax.vjp(f, h.astype(dtype), full[4].astype(dtype), gates)[1](
            cot.astype(dtype))
        return [np.asarray(o, np.float32) for o in out]

    exact = miss(plain, jnp.float32)
    for name, e, a, b in zip(("h", "w_down", "gates"), exact,
                             miss(mine, jnp.bfloat16),
                             miss(plain, jnp.bfloat16)):
        assert np.abs(a - e).max() <= 1.5 * np.abs(b - e).max(), name


def _eqns(jaxpr):
    """Every equation of a jaxpr and of its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("remat", [False, True])
def test_which_rows_the_blocks_gradient_moves_and_multiplies(remat):
    """The contract of ``ops/moe.py``'s docstring, read from the jaxpr of
    the block's gradient (after jax's own dead-code pass over a
    ``jax.checkpoint``). Exactly TWO gathers take an [A, D] operand: the
    forward's ``y[inverse]`` and the dispatch's backward ``g[inverse]``
    (three before the block owned its backward); the combine's backward
    gathers from [N, D], as the dispatch's forward does. Under
    remat the recompute runs the dispatch's gather and TWO grouped
    matmuls (gate, up), not three: nothing in the backward reads ``y``,
    so neither the down matmul nor ``y[inverse]`` is recomputed."""
    top_k = 3
    args = block_inputs("balanced")
    n, a_rows = BLOCK_B * BLOCK_S, BLOCK_B * BLOCK_S * top_k

    def loss(*a):
        return moe.moe_swiglu_dropless(*a, top_k=top_k)[0].sum()

    if remat:
        loss = jax.checkpoint(loss)
    eqns = list(_eqns(jax.make_jaxpr(jax.grad(loss, range(5)))(*args).jaxpr))
    gathered = [e.invars[0].aval.shape for e in eqns
                if e.primitive.name == "gather"]
    assert gathered.count((a_rows, BLOCK_D)) == 2, gathered
    assert gathered.count((n, BLOCK_D)) == 2 + remat, gathered
    # forward 3, backward 2 each, and the recompute's
    matmuls = [e for e in eqns if e.primitive.name.startswith("ragged_dot")]
    assert len(matmuls) == 3 + 6 + 2 * remat, [str(e) for e in matmuls]


def _gathers(eqns):
    """(operand rows, rows written) of every gather of whole rows."""
    return [(e.invars[0].aval.shape[0], e.outvars[0].aval.shape[0])
            for e in eqns if e.primitive.name == "gather"
            and len(e.outvars[0].aval.shape) == 2]


def _block_gradient(held, experts_here, top_k=2, tokens=1024, d=16):
    shapes = [(1, tokens, d), (d, 16), (experts_here, d, 8),
              (experts_here, d, 8), (experts_here, 8, d)]

    def loss(*a):
        return moe.moe_swiglu_dropless(*a, top_k=top_k, held=held)[0].sum()

    return jax.make_jaxpr(jax.grad(loss, range(5)))(
        *[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]).jaxpr


@pytest.mark.parametrize("held", [None, (0, 8), (8, 16)])
def test_a_caller_with_no_shorter_buffer_traces_no_loop(held):
    """A model that holds every expert, and a rank that holds half of them
    or more (twice its balanced share is every assignment), get the block
    they got: no ``while`` and no ``cond`` in the gradient's jaxpr, and
    the same four gathers, two of them from an [A, D] operand."""
    tokens, a_rows = 1024, 2048
    eqns = list(_eqns(_block_gradient(held, 16 if held is None else 8)))
    assert not [e for e in eqns if e.primitive.name in ("while", "cond")]
    assert sorted(_gathers(eqns)) == [
        (tokens, a_rows), (tokens, a_rows), (a_rows, a_rows), (a_rows, a_rows)]


def test_which_rows_a_rank_with_a_quarter_of_the_experts_moves():
    """Rank 1 of 4 (4 of 16 experts held): the buffer is C = half of the A
    assignments. The gradient's jaxpr has two ``while`` (the forward's and
    the backward's rounds) and every gather and grouped matmul of the
    block is in their bodies, once: the three gathers by ``order //
    top_k`` (dispatch, its recompute in the backward rule, the combine's
    backward) write [C, D]; the two moves by ``inverse`` read a [C, D]
    operand a choice at a time, ``top_k`` gathers of [N, D] each, and
    write no [A, D] rows; every grouped matmul is over C rows."""
    top_k, tokens, a_rows = 2, 1024, 2048
    c_rows = moe._buffer_rows(a_rows, 4, 16)
    assert c_rows == a_rows // 2
    jaxpr = _block_gradient((4, 8), 4)
    loops = [e for e in _eqns(jaxpr) if e.primitive.name == "while"]
    assert len(loops) == 2
    assert not [e for e in _eqns(jaxpr) if e.primitive.name == "cond"]
    inside = [e for loop in loops
              for e in _eqns(loop.params["body_jaxpr"].jaxpr)]
    assert sorted(_gathers(inside)) == (
        [(tokens, c_rows)] * 3 + [(c_rows, tokens)] * 2 * top_k)
    assert sorted(_gathers(_eqns(jaxpr))) == sorted(_gathers(inside))
    matmuls = [e for e in inside if e.primitive.name.startswith("ragged_dot")]
    # forward 3; in the backward rule gate and up again, then 2 each
    assert len(matmuls) == 3 + 2 + 6
    assert len([e for e in _eqns(jaxpr)
                if e.primitive.name.startswith("ragged_dot")]) == 11
    assert {e.invars[0].aval.shape[0] for e in matmuls} == {c_rows}
