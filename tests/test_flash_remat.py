"""The layer's checkpoint keeps the Pallas attention kernel's output and
logsumexp (PR 32: ``ops.attention.FLASH_OUT_NAME`` / ``FLASH_LSE_NAME``,
saved by ``models/transformer.py`` ``layer_of``'s policy), so the
backward's recompute launches no forward kernel: two ``pallas_call``s a
layer (forward, and since PR 38 the ONE backward kernel) where
``jax.checkpoint(layer)`` with no policy has three; the same gradients,
bit for bit; and a layer whose attention never took the kernel saves what
it saved before. A file of its own beside
``tests/test_smallthinker_kernels.py`` so that the two run on two workers.
CPU only: the kernels in the interpreter, float32, at the one 256-row tile
their rule gives a row of 256.

"Unpoliced" is the program's own ``forward`` with ``jax.checkpoint``'s
``policy`` argument dropped: ``jax.checkpoint(layer)``, what ``"full"``
was before; for ``"dots"`` the name policy alone is dropped.
"""

from __future__ import annotations

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu import models, ops
from ray_tpu.models import transformer

# ``ray_tpu.ops.attention`` the attribute is the dispatch function.
attention = importlib.import_module("ray_tpu.ops.attention")

T = 256

# (what the layers' attention is, the config): two plain causal layers of
# OLMoE's kind of block, dense; one period of SmallThinker's pattern, a
# global layer and three under a window of 64 keys.
KERNEL_CONFIGS = {
    "causal": lambda **kw: models.TransformerConfig(
        arch="llama", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=256, max_seq_len=T, dtype="float32",
        attn_impl="flash", **kw),
    "windowed": lambda **kw: models.smallthinker_21b_a3b(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_head=32, d_ff=32,
        n_experts=8, expert_top_k=3, vocab_size=256, max_seq_len=T,
        sliding_window=64, dtype="float32", attn_impl="flash", **kw),
}
# Layers in the body the jaxpr holds once: the scan's step is one layer,
# or one whole period unrolled.
LAYERS_IN_BODY = {"causal": 1, "windowed": 4}


def _inputs(cfg, seed=0):
    params = models.init_params(jax.random.PRNGKey(seed), cfg)
    # Larger than the program's N(0, 0.02), so attention moves the loss.
    params = dict(params, layers=jax.tree.map(lambda a: a * 5.0,
                                              params["layers"]))
    rows = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, T + 1), 0,
                              cfg.vocab_size)
    return params, rows


def _loss(cfg):
    return lambda params, rows: models.lm_loss(params, {"tokens": rows},
                                               cfg)[0]


@pytest.fixture
def unpoliced(monkeypatch):
    """Inside: ``forward`` checkpoints its layers with no name policy."""
    checkpoint = jax.checkpoint
    policies = jax.checkpoint_policies
    dots = policies.dots_with_no_batch_dims_saveable

    def without_names(fun, *, policy=None, **kw):
        return checkpoint(fun, policy=policy if policy is dots else None,
                          **kw)

    monkeypatch.setattr(jax, "checkpoint", without_names)
    monkeypatch.setattr(policies, "save_from_both_policies",
                        lambda first, names: first)


def _count(jaxpr, primitive: str) -> int:
    """Equations of ``primitive`` in ``jaxpr`` and every jaxpr under it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, primitive)
    return n


def _kernels_in_grad(cfg) -> int:
    params, rows = _inputs(cfg)
    return _count(jax.make_jaxpr(jax.grad(_loss(cfg)))(params, rows).jaxpr,
                  "pallas_call")


@pytest.mark.parametrize("remat_policy", ["full", "dots"])
@pytest.mark.parametrize("kind", sorted(KERNEL_CONFIGS))
def test_backward_runs_two_kernels_a_layer(kind, remat_policy, request):
    """Three until PR 38 (forward, dq, dk / dv), two since: the forward
    and the one backward kernel; unpoliced, the forward once more."""
    cfg = KERNEL_CONFIGS[kind](remat_policy=remat_policy)
    assert _kernels_in_grad(cfg) == 2 * LAYERS_IN_BODY[kind]
    request.getfixturevalue("unpoliced")
    assert _kernels_in_grad(cfg) == 3 * LAYERS_IN_BODY[kind]


@pytest.mark.parametrize("remat_policy", ["full", "dots"])
@pytest.mark.parametrize("kind", sorted(KERNEL_CONFIGS))
def test_gradients_equal_the_unpoliced_ones_exactly(kind, remat_policy,
                                                    request):
    cfg = KERNEL_CONFIGS[kind](remat_policy=remat_policy)
    params, rows = _inputs(cfg)
    loss, grads = jax.value_and_grad(_loss(cfg))(params, rows)
    request.getfixturevalue("unpoliced")
    want_loss, want = jax.value_and_grad(_loss(cfg))(params, rows)
    assert float(loss) == float(want_loss)
    moved = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        assert bool(jnp.array_equal(a, b)), jax.tree_util.keystr(path)
        moved += bool(jnp.any(a != 0))
    assert moved >= len(jax.tree.leaves(want)) - 1


@pytest.mark.parametrize("kind", sorted(KERNEL_CONFIGS))
def test_a_renamed_residual_brings_the_recompute_back(kind, monkeypatch):
    """The two names are a contract between ``ops/attention.py`` and the
    policy: with one changed on one side only, the forward kernel is back
    in the recompute, and nothing else says so."""
    cfg = KERNEL_CONFIGS[kind]()
    monkeypatch.setattr(transformer, "FLASH_LSE_NAME", "another_name")
    assert _kernels_in_grad(cfg) == 3 * LAYERS_IN_BODY[kind]


def test_the_names_are_exported_and_the_primal_has_none():
    assert ops.FLASH_OUT_NAME == attention.FLASH_OUT_NAME != ops.FLASH_LSE_NAME
    q = jnp.ones((1, T, 2, 32), jnp.float32)
    primal = jax.make_jaxpr(lambda q: ops.flash_attention(q, q, q))(q)
    assert _count(primal.jaxpr, "name") == 0
    assert _count(primal.jaxpr, "pallas_call") == 1


def _saved(cfg, capsys) -> list[str]:
    """The lines of ``print_saved_residuals`` (this jax exports the
    printer only): a shape and where it comes from, one a residual."""
    params, rows = _inputs(cfg)
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(_loss(cfg), params, rows)
    return capsys.readouterr().out.splitlines()


@pytest.mark.parametrize("remat_policy", ["full", "dots"])
@pytest.mark.parametrize("arch", ["gpt2", "llama"])
def test_a_layer_off_the_kernel_saves_what_it_saved(arch, remat_policy,
                                                    request, capsys):
    """The dense cells' path (``attn_impl="auto"``, T <= 1024: the
    materialised scores in causal query blocks) yields no named value:
    the residuals are the same list with the policy and without."""
    cfg = models.TransformerConfig(
        arch=arch, n_layers=2, d_model=64, n_heads=4, d_ff=128,
        vocab_size=256, max_seq_len=T, dtype="float32",
        remat_policy=remat_policy)
    assert cfg.attn_impl == "auto"
    params, rows = _inputs(cfg)
    with_policy = _saved(cfg, capsys)
    assert len(with_policy) > 4
    grad = jax.make_jaxpr(jax.grad(_loss(cfg)))(params, rows)
    assert _count(grad.jaxpr, "pallas_call") == 0
    request.getfixturevalue("unpoliced")
    assert with_policy == _saved(cfg, capsys)
    # And the same program: the jaxprs differ in the policy's repr alone.
    text = lambda jaxpr: re.sub(r"policy=.*", "policy=_", str(jaxpr))  # noqa: E731
    assert text(grad) == text(
        jax.make_jaxpr(jax.grad(_loss(cfg)))(params, rows))
