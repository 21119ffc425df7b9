"""Model library tests (tiny configs on the 8-device CPU mesh)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu import models
from ray_tpu.parallel.mesh import MeshConfig
from ray_tpu.parallel.sharding import infer_param_specs, make_shardings


@pytest.fixture(scope="module", params=["gpt2", "llama"])
def arch(request):
    return request.param


def _cfg(arch, **kw):
    base = dict(dtype="float32")
    base.update(kw)
    cfg = models.tiny(arch=arch, **base)
    if arch == "llama":
        cfg = models.tiny(arch="llama", n_kv_heads=2, **base)
    return cfg


def test_forward_shapes(arch):
    cfg = _cfg(arch)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = models.forward(params, toks, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_causality(arch):
    """Changing a future token must not affect earlier logits."""
    cfg = _cfg(arch)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab_size)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % cfg.vocab_size)
    a = models.forward(params, toks, cfg)
    b = models.forward(params, toks2, cfg)
    np.testing.assert_allclose(a[:, :-1], b[:, :-1], atol=1e-4)


def test_train_step_learns(arch):
    """A few steps on a fixed batch reduces loss."""
    cfg = _cfg(arch)
    opt = optax.adamw(1e-2)
    state = models.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step = jax.jit(models.make_train_step(cfg, opt))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0,
                                     cfg.vocab_size)
    }
    state, m0 = step(state, batch)
    for _ in range(10):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])
    assert int(state["step"]) == 11
    assert bool(jnp.isfinite(m["grad_norm"]))


def test_loss_mask():
    cfg = _cfg("gpt2")
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    mask = jnp.ones((2, 16)).at[:, 8:].set(0)
    full, _ = models.lm_loss(params, {"tokens": toks}, cfg)
    masked, _ = models.lm_loss(params, {"tokens": toks, "mask": mask}, cfg)
    assert not np.isclose(float(full), float(masked))


def test_sharded_train_step(arch):
    """pjit the train step over a 2x2x2 dp×fsdp×tensor mesh."""
    cfg = _cfg(arch)
    mesh = MeshConfig(data=2, fsdp=2, tensor=2).build()
    opt = optax.adamw(1e-2)
    state = models.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    base = models.partition_specs(cfg)
    specs = infer_param_specs(state["params"], mesh, base)
    shardings = make_shardings(mesh, specs)
    state = {
        "params": jax.tree.map(jax.device_put, state["params"], shardings),
        "opt_state": state["opt_state"],
        "step": state["step"],
    }
    step = jax.jit(models.make_train_step(cfg, opt, mesh=mesh),
                   donate_argnums=(0,))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0,
                                     cfg.vocab_size)
    }
    state, metrics = step(state, batch)
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))

    # Sharded result matches single-device result.
    state2 = models.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step2 = jax.jit(models.make_train_step(cfg, opt))
    state2, _ = step2(state2, batch)
    state2, m2 = step2(state2, batch)
    np.testing.assert_allclose(float(metrics["loss"]), float(m2["loss"]),
                               rtol=2e-3)


def test_decode_matches_forward(arch):
    """Prefill+decode through the KV cache == full forward logits."""
    cfg = _cfg(arch)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab_size)
    full = models.forward(params, toks, cfg)

    cache = models.init_kv_cache(cfg, 2, 16)
    logits_p, cache = models.decode_step(params, toks[:, :6], cache, cfg)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(full[:, :6]),
                               atol=2e-3)
    for t in range(6, 10):
        logits_t, cache = models.decode_step(params, toks[:, t:t + 1], cache,
                                             cfg)
        np.testing.assert_allclose(np.asarray(logits_t[:, 0]),
                                   np.asarray(full[:, t]), atol=2e-3)


def test_generate(arch):
    cfg = _cfg(arch)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                cfg.vocab_size)
    out = models.generate(params, prompt, cfg, max_new_tokens=7)
    assert out.shape == (2, 12)
    np.testing.assert_array_equal(np.asarray(out[:, :5]), np.asarray(prompt))


def test_partition_specs_mirror_params(arch):
    cfg = _cfg(arch)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    specs = models.partition_specs(cfg)
    # Same tree structure.
    jax.tree.map(lambda p, s: None, params, specs,
                 is_leaf=lambda x: x is None or not isinstance(x, dict))


def test_param_counts():
    assert 120e6 < models.gpt2_small().num_params() < 170e6
    assert 6e9 < models.llama2_7b().num_params() < 7.5e9


def test_chunked_ce_matches_dense_loss():
    """loss_chunk path must agree with the fused-logits path (same params,
    same batch) — it is a memory layout change, not a numerics change."""
    import jax
    import numpy as np

    from ray_tpu.models import transformer as tfm

    c_dense = tfm.tiny(dtype="float32")
    c_chunk = tfm.tiny(dtype="float32", loss_chunk=64)
    params = tfm.init_params(jax.random.PRNGKey(0), c_dense)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                          c_dense.vocab_size)}
    l1, m1 = tfm.lm_loss(params, batch, c_dense)
    l2, m2 = tfm.lm_loss(params, batch, c_chunk)
    assert np.allclose(float(l1), float(l2), rtol=1e-5)
    assert np.allclose(float(m1["accuracy"]), float(m2["accuracy"]))
    # Gradients agree too.
    g1 = jax.grad(lambda p: tfm.lm_loss(p, batch, c_dense)[0])(params)
    g2 = jax.grad(lambda p: tfm.lm_loss(p, batch, c_chunk)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_grad_accumulation_matches_full_batch():
    """accum_steps=2 over a batch of 4 must match the plain step on the
    same 4 rows (same grads -> same params after one optimizer apply)."""
    import jax
    import numpy as np

    from ray_tpu.models import transformer as tfm

    cfg = tfm.tiny(dtype="float32", loss_chunk=64)
    opt = optax.adam(1e-3)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}

    s0 = models.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step_full = jax.jit(tfm.make_train_step(cfg, opt))
    s_full, m_full = step_full(s0, batch)

    s0b = models.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step_acc = jax.jit(tfm.make_train_step(cfg, opt, accum_steps=2))
    s_acc, m_acc = step_acc(s0b, batch)

    assert np.allclose(float(m_full["loss"]), float(m_acc["loss"]),
                       rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_full["params"]),
                    jax.tree.leaves(s_acc["params"])):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    # UNEVEN mask: microbatches must weight by valid-token count to
    # match the full-batch per-token mean.
    mask = np.ones((4, 33), np.float32)
    mask[2:, 5:] = 0.0  # rows 2-3 mostly masked
    mb = {"tokens": toks, "mask": jnp.asarray(mask)}
    s1, mf = step_full(models.init_train_state(jax.random.PRNGKey(0), cfg,
                                               opt), mb)
    s2, ma = step_acc(models.init_train_state(jax.random.PRNGKey(0), cfg,
                                              opt), mb)
    assert np.allclose(float(mf["loss"]), float(ma["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_grad_accumulation_moe_keeps_router_aux():
    """Accumulated MoE steps must still report router_aux (generic
    metric accumulation, not a hardcoded key set)."""
    import jax

    from ray_tpu.models import transformer as tfm

    cfg = tfm.tiny_moe(dtype="float32")
    opt = optax.adam(1e-3)
    s0 = models.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step = jax.jit(tfm.make_train_step(cfg, opt, accum_steps=2))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                              cfg.vocab_size)
    _, m = step(s0, {"tokens": toks})
    assert "router_aux" in m
    assert np.isfinite(float(m["router_aux"]))


def test_fused_ce_matches_checkpoint_ce():
    """ce_impl="fused" (analytic dlogits in the forward scan) must agree
    with ce_impl="checkpoint" (jax.checkpoint recompute) in loss AND
    gradients, including z_loss and a padding mask."""
    import jax
    import numpy as np

    from ray_tpu.models import transformer as tfm

    c_f = tfm.tiny(dtype="float32", loss_chunk=64, ce_impl="fused")
    c_c = tfm.tiny(dtype="float32", loss_chunk=64, ce_impl="checkpoint")
    params = tfm.init_params(jax.random.PRNGKey(0), c_f)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                              c_f.vocab_size)
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (2, 33)) > 0.2)
    batch = {"tokens": toks, "mask": mask.astype(np.float32)}
    for z in (0.0, 1e-3):
        l1, m1 = tfm.lm_loss(params, batch, c_f, z_loss=z)
        l2, m2 = tfm.lm_loss(params, batch, c_c, z_loss=z)
        assert np.allclose(float(l1), float(l2), rtol=1e-5), z
        assert np.allclose(float(m1["accuracy"]), float(m2["accuracy"]))
        g1 = jax.grad(lambda p: tfm.lm_loss(p, batch, c_f, z_loss=z)[0])(
            params)
        g2 = jax.grad(lambda p: tfm.lm_loss(p, batch, c_c, z_loss=z)[0])(
            params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def _materialised_oracle(cfg, batch, z):
    """``cross_entropy_loss`` on ``forward``'s whole logits: what
    ``lm_loss`` was at ``loss_chunk`` 0 before the head and the loss were
    one op, and the mathematics they are held to."""
    from ray_tpu.models import transformer as tfm

    toks, mask = batch["tokens"], batch.get("mask")

    def loss(p):
        logits = tfm.forward(p, toks[:, :-1], cfg)
        return tfm.cross_entropy_loss(
            logits, toks[:, 1:], mask=None if mask is None else mask[:, 1:],
            z_loss=z)
    return loss


@pytest.mark.parametrize("arch,masked,z,loss_chunk", [   # gpt2 is tied
    ("gpt2", False, 0.0, 0), ("gpt2", True, 1e-3, 24), ("gpt2", False, 0.0, 24),
    ("llama", True, 0.0, 0), ("llama", False, 1e-3, 24),
    ("llama", True, 1e-3, 0)])
def test_head_loss_is_the_materialised_cross_entropy(arch, masked, z,
                                                     loss_chunk):
    """The loss, every metric and the gradients of ``lm_loss`` are
    ``cross_entropy_loss``'s on materialised logits, at the default
    ``loss_chunk`` 0 (one block of all the rows) and in three blocks whose
    rows (24) do not divide the batch's 64, and the un-differentiated call
    gives the differentiated one's loss."""
    from ray_tpu.models import transformer as tfm

    assert _cfg(arch).loss_chunk == 0
    cfg = _cfg(arch, loss_chunk=loss_chunk)
    assert cfg.tied == (arch == "gpt2") and cfg.ce_impl == "fused"
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                          cfg.vocab_size)}
    if masked:
        batch["mask"] = (jax.random.uniform(jax.random.PRNGKey(2), (2, 33))
                         > 0.2).astype(np.float32)
    oracle = _materialised_oracle(cfg, batch, z)

    def program(p):
        return tfm.lm_loss(p, batch, cfg, z_loss=z)

    (l0, m0), g0 = jax.value_and_grad(oracle, has_aux=True)(params)
    (l1, m1), g1 = jax.value_and_grad(program, has_aux=True)(params)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    assert sorted(m1) == sorted(m0)
    for name in m0:
        # exp(loss) carries the loss's last bit 5.5 times over
        np.testing.assert_allclose(float(m1[name]), float(m0[name]),
                                   rtol=1e-5, err_msg=name)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-6)
    undifferentiated, _ = program(params)
    np.testing.assert_allclose(float(undifferentiated), float(l1), rtol=1e-6)


def _avals(jaxpr, found, primitive=None):
    """Every (shape, dtype) a jaxpr's equations (those of ``primitive``)
    produce, its sub-jaxprs' included."""
    for eqn in jaxpr.eqns:
        if primitive in (None, eqn.primitive.name):
            found.update((v.aval.shape, str(v.aval.dtype))
                         for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _avals(sub, found, primitive)
    return found


def test_default_head_loss_engages_and_keeps_no_whole_float32_logits():
    """``jax.grad(lm_loss)`` at the default: the whole float32 logits
    exist once, as the op's one block, and nothing scatters into them
    (jax's transposed ``take_along_axis``, which the materialised loss's
    gradient holds, is what cost the copies); with a ``loss_chunk`` no
    float32 array of all the rows by the vocabulary exists, only a
    block's."""
    from ray_tpu.models import transformer as tfm

    cfg = _cfg("llama", vocab_size=320)     # no other width of the model
    V = cfg.vocab_size
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 41), jnp.int32)}    # 80 rows, D is 64

    def produced(loss, primitive=None):
        return _avals(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, set(),
                      primitive)

    def program(loss_chunk):
        c = dataclasses.replace(cfg, loss_chunk=loss_chunk)
        return lambda p: tfm.lm_loss(p, batch, c)[0]

    def oracle(p):
        return _materialised_oracle(cfg, batch, 0.0)(p)[0]

    whole = {((2, 40, V), "float32"), ((80, V), "float32")}
    assert whole & produced(oracle, "scatter-add")
    assert not whole & produced(program(0), "scatter-add")
    assert ((2, 40, V), "float32") in produced(program(0))    # the block
    blocks = produced(program(24))
    assert not whole & blocks
    assert ((24, V), "float32") in blocks


def test_head_loss_under_a_mesh_is_one_block_on_each_devices_own_rows():
    """Two devices share the batch's rows: the default is one block of all
    the rows as they lie (blocks would regroup rows across devices), the
    block's logits keep ``forward``'s sharding constraint, and loss and
    gradients are the unsharded program's."""
    from ray_tpu.models import transformer as tfm

    cfg = _cfg("llama")
    mesh = MeshConfig(data=1, fsdp=2).build(jax.devices()[:2])
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                          cfg.vocab_size)}
    grad = jax.jit(jax.value_and_grad(
        lambda p: tfm.lm_loss(p, batch, cfg, mesh=mesh)[0]))
    text = grad.lower(params).as_text()
    logits = f"tensor<2x32x{cfg.vocab_size}xf32>"
    assert any("sharding_constraint" in line and logits in line
               for line in text.splitlines()), "the logits are unconstrained"
    l1, g1 = grad(params)
    l0, g0 = jax.value_and_grad(
        lambda p: _materialised_oracle(cfg, batch, 0.0)(p)[0])(params)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-6)


def test_fused_clip_adamw_matches_optax():
    """ops.optim.FusedClipAdamW must reproduce
    optax.chain(clip_by_global_norm, adamw) exactly — it is an HBM-pass
    fusion, not a new optimizer."""
    from ray_tpu.ops.optim import FusedClipAdamW

    cfg = models.tiny()
    opt_ref = optax.chain(optax.clip_by_global_norm(1.0),
                          optax.adamw(3e-4, weight_decay=0.1))
    fused = FusedClipAdamW(learning_rate=3e-4, weight_decay=0.1,
                           clip_norm=1.0)
    p_ref = p_f = models.init_params(jax.random.PRNGKey(0), cfg)
    s_ref, s_f = opt_ref.init(p_ref), fused.init(p_ref)
    rngs = jax.random.split(jax.random.PRNGKey(5), 4)
    for i in range(4):
        # Alternate below/above the clip threshold so both branches of
        # the inline clip are exercised.
        g = jax.tree.map(
            lambda x, i=i: jax.random.normal(rngs[i], x.shape, x.dtype)
            * (3.0 if i % 2 else 0.01),
            p_ref,
        )
        u, s_ref = opt_ref.update(g, s_ref, p_ref)
        p_ref = jax.tree.map(lambda a, b: a + b.astype(a.dtype), p_ref, u)
        p_f, s_f, gnorm = fused.apply(g, s_f, p_f)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_f)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-7)
        assert float(gnorm) > 0.0


def test_fused_adamw_in_train_step():
    """make_train_step detects the fused optimizer and trains (loss
    decreases; grad_norm metric comes from the shared reduction)."""
    from ray_tpu.ops.optim import FusedClipAdamW

    cfg = models.tiny(dtype="float32")
    fused = FusedClipAdamW(learning_rate=1e-2, weight_decay=0.0)
    state = models.init_train_state(jax.random.PRNGKey(0), cfg, fused)
    step = jax.jit(models.make_train_step(cfg, fused))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                                          cfg.vocab_size)}
    state, m0 = step(state, batch)
    for _ in range(10):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])
    assert float(m["grad_norm"]) > 0.0
    assert int(state["step"]) == 11
