"""The state-space scan's Pallas kernels (``ray_tpu/ops/state_space.py``
``_ssm_fwd_kernel`` / ``_ssm_bwd_kernel``), interpreted on the CPU at ONE
lane-whole shape (one group of two 64-wide heads, state 128, chunk 128, a
row of 300 that is padded): against the token-by-token recurrence forward
and in all six gradients, under mild decays and under ones whose ``exp(-G)``
overflows float32; what the forward keeps; the rules that choose between
the kernels and the plain forms, the scan's, the gated group norm's and the
chain's (one question, ``linear_attention._one_tpu``, and each op's own
shapes); and the scopes the layer's nine ``pallas_call``s are traced under
in a model's gradient."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu import models
from ray_tpu.ops import linear_attention as la
from ray_tpu.ops import state_space as ss
from test_state_space import _step_by_step

B, T, H, P, G, S, CHUNK = 1, 300, 2, 64, 1, 128, 128
RATES = {"mild": (0.01, 0.5), "underflows": (2.0, 30.0)}


def _operands(seed: int, rates, t: int = T):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (B, t, H, P))
    b, c = (jax.random.normal(k[i], (B, t, G, S)) for i in (1, 2))
    dt = 3 * jax.nn.softplus(jax.random.normal(k[3], (B, t, H)))
    return x, dt, -dt * jnp.asarray(rates), b, c, jax.random.normal(k[4], (H,))


@pytest.fixture
def kernels(monkeypatch):
    """``ssm_scan`` with the rule's answer forced: the kernels, which the
    CPU interprets."""
    monkeypatch.setattr(ss, "_takes_kernels", lambda *a: True)
    return jax.jit(lambda *ops: ss.ssm_scan(*ops, chunk=CHUNK))


@pytest.mark.parametrize("rates", RATES.values(), ids=RATES)
def test_the_kernels_are_the_recurrence_forward_and_backward(rates, kernels):
    ops = _operands(3, rates)
    if rates[-1] > 1:       # exp(-G) of a chunk is inf in float32
        assert float(ops[2][0, :CHUNK].sum(0).min()) < -200
    want, got = _step_by_step(*ops), kernels(*ops)
    assert got.shape == (B, T, H, P) and bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = [jax.jit(jax.grad(lambda *o, f=f: (f(*o) * w).sum(),
                              argnums=tuple(range(6))))(*ops)
             for f in (kernels, _step_by_step)]
    for name, mine, theirs in zip("x dt a b c skip".split(), *grads):
        assert bool(jnp.isfinite(mine).all()), name
        assert float(jnp.abs(mine - theirs).max()) \
            < 5e-5 * max(1.0, float(jnp.abs(theirs).max())), name


def test_the_kernels_padded_tail_writes_nothing(kernels):
    ops = _operands(5, RATES["mild"])
    whole = kernels(*ops)
    short = kernels(*(o[:, :2 * CHUNK] for o in ops[:5]), ops[5])
    assert float(jnp.abs(whole[:, :2 * CHUNK] - short).max()) < 1e-5


def test_the_kernels_forward_keeps_six_arrays_and_no_chunk_matrix():
    """The operands as they came and the chunks' START states, [B, chunks,
    state, heads x channels] float32 (the plain form's bytes): nothing of a
    head's [chunk, chunk] matrices."""
    x, dt, a, b, c, _ = _operands(4, RATES["mild"], 3 * CHUNK)
    flat = x.reshape(B, -1, H * P), dt, a, b.reshape(B, -1, G * S), c.reshape(
        B, -1, G * S)
    y, kept = ss._kernel_scan_fwd(*flat)
    assert y.shape == flat[0].shape and len(kept) == 6
    assert all(k is o for k, o in zip(kept, flat))
    assert kept[5].shape == (B, 3, S, H * P) and kept[5].dtype == jnp.float32
    assert float(jnp.abs(kept[5][:, 0]).max()) == 0         # S_0 = 0
    assert float(jnp.abs(kept[5][:, 1]).max()) > 0


def test_one_rule_sends_a_cpu_a_mesh_and_an_odd_width_to_the_plain_form(
        monkeypatch):
    x, _, _, b, *_ = ops = _operands(6, RATES["mild"], CHUNK)
    assert not ss._takes_kernels(x, b, CHUNK)               # a CPU
    monkeypatch.setattr(ss, "_kernel_scan", None)           # never reached
    assert ss.ssm_scan(*ops, chunk=CHUNK).shape == x.shape
    spread = jax.device_put(x, NamedSharding(
        Mesh(jax.devices()[:2], ("d",)), PartitionSpec()))

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    assert ss._takes_kernels(x, b, CHUNK)                   # ONE TPU chip
    assert not ss._takes_kernels(spread, b, CHUNK)          # a mesh over x
    assert not ss._takes_kernels(x, b, CHUNK // 2)          # another chunk
    assert not ss._takes_kernels(x, b[..., :64], CHUNK)     # another state
    assert not ss._takes_kernels(x[..., :48], b, CHUNK)     # heads of 48
    assert not ss._takes_kernels(x[:, :, :1], b, CHUNK)     # half a tile
    # the cell's own: 64 heads of 64 in 8 groups
    assert ss._takes_kernels(jnp.zeros((1, 8, 64, 64)),
                             jnp.zeros((1, 8, 8, 128)), CHUNK)
    # the norm's own shapes, under the same question (``la._one_tpu``): a
    # group whole 128-lane tiles that divides a grid step's 512 lanes
    flat = jnp.zeros((1, 8, 4096))
    assert ss._norm_takes_kernels(flat, 512)                # the cell's own
    assert ss._norm_takes_kernels(flat, 128)
    assert not ss._norm_takes_kernels(flat, 1024)           # over a step
    assert not ss._norm_takes_kernels(flat[..., :768], 96)  # a group of 96
    assert not ss._norm_takes_kernels(flat[..., :768], 384)  # a 256 step
    assert not ss._norm_takes_kernels(jax.device_put(flat, spread.sharding),
                                      512)                  # a mesh
    # and the chain's: whole 128-lane tiles, taps the halo holds
    ran = []
    monkeypatch.setattr(la, "_chain_kernels",
                        lambda *a: ran.append(a[1].shape))
    monkeypatch.setattr(la, "_chain", lambda *a: None)
    wide, taps = jnp.zeros((1, 8, 6144)), jnp.zeros((4, 6144))
    la.flat_conv_silu(wide, taps, taps[0])                  # the cell's own
    la.flat_conv_silu(wide[..., :96], taps[:, :96])         # 96 lanes
    # nine taps are the most the halo's eight rows hold: ten
    la.flat_conv_silu(wide, jnp.zeros((la._CONV_HALO + 2, 6144)))
    la.flat_conv_silu(jax.device_put(wide, spread.sharding), taps)  # a mesh
    assert ran == [(4, 6144)]
    monkeypatch.undo()
    assert not ss._norm_takes_kernels(flat, 512)            # a CPU
    monkeypatch.setattr(la, "_chain_kernels", None)         # never reached
    assert la.flat_conv_silu(wide, taps, taps[0]).shape == wide.shape


def _pallas_calls(jaxpr, under=""):
    """(the scopes an equation is traced under, its outputs' shapes) of
    every ``pallas_call`` of a jaxpr, through its sub-jaxprs."""
    found = []
    for e in jaxpr.eqns:
        here = f"{under}/{e.source_info.name_stack}"
        if e.primitive.name == "pallas_call":
            found.append((here, [v.aval.shape for v in e.outvars]))
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _pallas_calls(sub, here)
    return found


def test_the_kernels_keep_the_scopes_the_readers_read(monkeypatch):
    """``step_kda_core_ms``, ``kda_core_peak_share`` and
    ``step_attn_kernel_ms`` read what runs under ``attn`` / ``attn_linear``
    / ``attn_core``: the forward's kernel, the recomputed forward's and
    the backward's. The chain's three run under ``kda_conv``
    (``step_kda_conv_ms``) and the gated group norm's three under
    ``kda_gate`` (``step_kda_gate_ms``), all nine under ``attn_linear``."""
    monkeypatch.setattr(la, "_one_tpu", lambda a: True)    # the ONE question
    cfg = models.nemotron_3_nano_30b_a3b(
        n_layers=1, d_model=128, n_heads=H, n_kv_heads=H, d_head=P,
        kda_heads=H, kda_head_dim=P, ssm_state=S, ssm_groups=G,
        ssm_chunk=CHUNK, d_ff=24, d_ff_shared=40, n_experts=4,
        expert_top_k=2, vocab_size=64, max_seq_len=CHUNK, dtype="float32")
    assert cfg.layer_mixers == ("ssm",)
    params = jax.eval_shape(lambda: models.init_params(jax.random.PRNGKey(0),
                                                       cfg))
    rows = jax.ShapeDtypeStruct((1, CHUNK + 1), jnp.int32)
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(
        lambda p, r: models.lm_loss(p, {"tokens": r}, cfg)[0]))(
            params, rows).jaxpr)
    assert len(calls) == 9
    assert all("attn/attn_linear/" in under for under, _ in calls)
    assert "ssm_carry" not in "".join(under for under, _ in calls)
    by_scope = {scope: [c for c in calls if f"attn_linear/{scope}" in c[0]]
                for scope in ("kda_conv", "attn_core", "kda_gate")}
    assert [len(found) for found in by_scope.values()] == [3, 3, 3]
    flat, wide = (1, CHUNK, H * P), (1, CHUNK, H * P + 2 * G * S)
    # the chain: y, y again, then dx with the taps' and the bias' sums
    assert [shapes for _, shapes in by_scope["kda_conv"]] == [
        [wide], [wide], [wide, (1, 4 + 1, wide[2])]]
    # the norm: y, y again, then d_y, d_z and the weight's eight-row sums
    assert [shapes for _, shapes in by_scope["kda_gate"]] == [
        [flat], [flat], [flat, flat, (1, 8, H * P)]]
    calls = by_scope["attn_core"]
    states, rows_out = (1, 1, S, H * P), (1, G, 1, H // G, CHUNK)
    assert [shapes[1] for _, shapes in calls] == [states, states, rows_out]
    assert sum("transpose" in under for under, _ in calls) == 2
