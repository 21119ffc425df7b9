"""The two plain references against ``models.forward`` at ``tiny``."""

from __future__ import annotations

import pytest


@pytest.mark.parametrize("arch,kwargs", [
    ("llama", {"arch": "llama", "n_kv_heads": 2}),
    ("gpt2", {}),
])
def test_reference_agrees_with_the_program_in_float32(arch, kwargs):
    import jax
    import jax.numpy as jnp

    from chipbench import spec
    from chipbench.reference import _common
    from ray_tpu import models

    # float32 compute on both sides: only the order of operations
    # differs, so 1e-5 on logits of magnitude ~1 (float32 has 2^-24).
    cfg = models.tiny(dtype="float32", **kwargs)
    params = models.init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0,
                              cfg.vocab_size)
    ref = spec.load_part("reference", arch)
    want = models.forward(params, toks[:, :-1], cfg)
    got = ref.forward(params, toks[:, :-1], cfg)
    assert float(jnp.abs(got - want).max()) < 1e-5
    loss = float(_common.next_token_loss(got, toks))
    want_loss = float(models.lm_loss(params, {"tokens": toks}, cfg)[0])
    assert loss == pytest.approx(want_loss, abs=1e-5)


def test_reference_attention_in_blocks_equals_one_block():
    import jax
    import jax.numpy as jnp

    from chipbench.reference import _common

    q, k, v = (jax.random.normal(kk, (1, 48, 2, 8), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    a = _common.causal_attention(q, k, v, block_q=16)
    b = _common.causal_attention(q, k, v, block_q=1024)
    assert float(jnp.abs(a - b).max()) < 1e-6


def test_bf16_program_stays_inside_the_training_tolerance():
    """The tolerance the train cells hold the program to must hold for
    bfloat16 compute against the float32 reference and fail for a broken
    program: at tiny size bf16 is well inside it, a dropped layer is not."""
    import jax

    from chipbench import spec
    from chipbench.drivers import train_job
    from chipbench.reference import _common
    from ray_tpu import models

    cfg = models.tiny(arch="llama", n_kv_heads=2)
    params = models.init_params(jax.random.PRNGKey(3), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0,
                              cfg.vocab_size)
    ref = spec.load_part("reference", "llama")
    want = float(_common.next_token_loss(
        ref.forward(params, toks[:, :-1], cfg), toks))
    got = float(models.lm_loss(params, {"tokens": toks}, cfg)[0])
    assert abs(got - want) <= train_job.LOSS_REFERENCE_TOLERANCE
    broken = dict(params, layers=jax.tree.map(lambda a: a * 4.0,
                                              params["layers"]))
    bad = float(models.lm_loss(broken, {"tokens": toks}, cfg)[0])
    assert abs(bad - want) > train_job.LOSS_REFERENCE_TOLERANCE
