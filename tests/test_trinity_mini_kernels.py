"""Trinity-Mini's 2,048 window through the Pallas kernels (interpret
mode) against the reference's materialised mask, the tiles the kernels
visit at the cell's 16,384 positions, and the scopes this model opens
(``attn_gate``, ``post_norm``) on the compiled step's instructions. A file
of its own beside ``tests/test_trinity_mini.py`` so that the two run on
two workers. CPU only, float32."""

from __future__ import annotations

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench import scopes
from chipbench.reference import afmoe as reference
from ray_tpu import models
from ray_tpu.models import transformer

T, WINDOW = 64, 16


def small(**kw):
    """``tests/test_trinity_mini.py``'s model: published layers 1 to 5 at
    test size."""
    base = dict(
        n_layers=5, first_layer=1, n_dense_layers=1, d_model=64, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=32, d_ff_dense=96, d_ff_shared=48,
        n_experts=8, expert_top_k=3, vocab_size=256, max_seq_len=T,
        sliding_window=WINDOW, embed_scale=8.0, experts_held=(1, 4),
        dtype="float32")
    base.update(kw)
    return models.trinity_mini_26b_a3b(**base)

attention = importlib.import_module("ray_tpu.ops.attention")


# -- the window through the kernels -------------------------------------------------

def _tiles(t, block, window):
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    visible = (j <= i) & (i - window < j)
    tiles = visible.reshape(t // block, block, t // block, block)
    causal = (j <= i).reshape(t // block, block, t // block, block)
    return int(tiles.any((1, 3)).sum()), int(causal.any((1, 3)).sum())


@pytest.mark.parametrize("block_q,block_k", [(1024, 1024), (512, 1024)])
def test_window_2048_through_the_kernels_at_a_row_longer_than_two_windows(
        block_q, block_k):
    """The Pallas kernels (interpret mode) under the published window at
    5,120 positions, GQA 2 query heads on one key head as the program
    hands them over (k and v repeated), against the reference's
    materialised mask ``i - 2048 < j <= i``: forward, dq, dk, dv."""
    t, window = 5120, 2048
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, g = (jax.random.normal(k, (1, t, 2, 128), jnp.float32)
            for k in keys[:2])
    k1, v1 = (jax.random.normal(k, (1, t, 1, 128), jnp.float32)
              for k in keys[2:])

    def kernels(q, k1, v1):
        k, v = jnp.repeat(k1, 2, axis=2), jnp.repeat(v1, 2, axis=2)
        return attention.flash_attention(q, k, v, True, block_q, block_k,
                                         window)

    want = reference._attention(q, k1, v1, window)
    assert float(jnp.abs(kernels(q, k1, v1) - want).max()) < 1e-5
    got_g = jax.grad(lambda *a: (kernels(*a) * g).sum(), (0, 1, 2))(q, k1, v1)
    want_g = jax.grad(lambda *a: (reference._attention(*a, window) * g).sum(),
                      (0, 1, 2))(q, k1, v1)
    for name, a, b in zip(("dq", "dk", "dv"), got_g, want_g):
        assert float(jnp.abs(a - b).max()) < 1e-4, name


def test_the_cells_tiles_at_16384():
    """1,024-row tiles at T = 16,384 (the rule's choice at width 128,
    bfloat16): a 2,048 window leaves 45 of the 136 causal tiles with a
    visible pair, so the kernels skip 91 (SmallThinker's 4,096: 66)."""
    for kernel in ("fwd", "bwd"):
        assert attention._flash_tiles(kernel, 16384, 16384, 128,
                                      jnp.bfloat16) == (1024, 1024)
    assert _tiles(16384, 1024, 2048) == (45, 136)
    assert _tiles(16384, 1024, 4096) == (70, 136)
    computed = 0
    for i in range(16):
        first, last = attention._visible_blocks(
            i, 1024, 1024, 16, *attention._window_reach(2048, True))
        computed += last - first + 1
    assert computed == 45


# -- scopes -----------------------------------------------------------------------

def _step_text(cfg) -> str:
    opt = optax.adamw(1e-3)
    state = jax.eval_shape(
        lambda k: models.init_train_state(k, cfg, opt), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, T + 1), jnp.int32)}
    step = jax.jit(models.make_train_step(cfg, opt))
    return step.trace(state, batch).lower().compile().as_text()


def test_the_new_scopes_are_on_the_compiled_steps_instructions():
    """``attn_gate`` inside ``attn`` and ``post_norm`` inside ``attn`` and
    inside ``mlp`` / ``moe``, in every pass; the head norms under
    ``attn_pos``; every such instruction is still ``attn``'s, ``mlp``'s or
    ``moe``'s to the benchmark's rule and none reads ``unscoped``."""
    assert transformer.SCOPE_FILES[0] == transformer.__file__
    assert not {transformer.GATE_SCOPE, transformer.POST_NORM_SCOPE} & set(
        scopes.PARTS)
    names = re.findall(r'op_name="([^"]*)"', _step_text(small()))
    found = set()
    for name in names:
        pieces = re.split(r"[/()]", name)
        for sub in (transformer.GATE_SCOPE, transformer.POST_NORM_SCOPE,
                    "attn_pos"):
            if sub in pieces:
                part, ps = scopes.classify(name)
                assert part in ("attn", "mlp", "moe"), name
                if sub != transformer.POST_NORM_SCOPE:
                    assert part == "attn", name
                found.add((sub, part, ps))
    for ps in scopes.PASSES:
        assert (transformer.GATE_SCOPE, "attn", ps) in found, sorted(found)
        assert ("attn_pos", "attn", ps) in found
        for part in ("attn", "mlp", "moe"):
            assert (transformer.POST_NORM_SCOPE, part, ps) in found, (
                part, ps, sorted(found))
    for path in ("attn/attn_window/attn_gate", "attn/attn_full/attn_gate",
                 "attn/attn_window/post_norm", "moe/post_norm",
                 "mlp/post_norm"):
        assert any(path in n for n in names), path
    # what the step runs that no part claims is what any model's step has
    # (the step counter, the loss's scalars): nothing of a block
    # (the step's arguments are named by their place in the state)
    def loose(names):
        return {re.sub(r"\d+", "", n) for n in names
                if scopes.classify(n)[0] == scopes.UNSCOPED
                and not n.startswith(("state[", "batch["))}

    plain = re.findall(r'op_name="([^"]*)"', _step_text(small(
        attn_gate=False, post_norm=False, qk_norm=False, embed_scale=1.0)))
    assert loose(names) <= loose(plain)


def test_a_model_without_them_opens_neither_scope():
    text = _step_text(models.tiny(arch="llama"))
    assert "attn_gate" not in text and "post_norm" not in text
