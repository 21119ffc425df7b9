"""The gated delta rule's Pallas kernels (``ray_tpu/ops/linear_attention.py``
``_by_kernels``) in the interpreter on the CPU, at 128-wide heads: forward
and the gradients of all five operands against the token-by-token
recurrence (``chipbench/reference/kimi_linear.py`` ``delta_rule``) and
against the XLA scan, a row that is no whole number of chunks, the strong
end of the decay, the worst case of the triangular inverse, causality, the
dtypes of a train step, and the choice ``gated_delta_rule`` makes between
the kernels and the scan. Float32 operands under ``highest`` precision:
the tolerances are float32 rounding. ONE shape for nearly everything
([1, 200, 2, 128]: three chunks + 8 positions, padded to four), so the
interpreter compiles the two kernels once.
"""

from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest

from chipbench.reference import kimi_linear as reference
from ray_tpu.ops import linear_attention as la

TOL = 1e-5
OPERANDS = ("q", "k", "v", "g", "beta")
T, H, D = 200, 2, 128


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def operands(seed: int, *, decay: float = 0.3, t: int = T, h: int = H):
    """q, k l2-normed, v normal, g in (-decay, 0] a channel, beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (la.l2_norm(jax.random.normal(ks[0], (1, t, h, D))),
            la.l2_norm(jax.random.normal(ks[1], (1, t, h, D))),
            jax.random.normal(ks[2], (1, t, h, D)),
            -decay * jax.random.uniform(ks[3], (1, t, h, D)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, h))))


WEIGHT = jax.random.normal(jax.random.PRNGKey(9), (1, T, H, D))


def _gradients(fn):
    return jax.jit(jax.grad(lambda *a: (fn(*a) * WEIGHT).sum(),
                            argnums=range(5)))


kernels = jax.jit(la._by_kernels)
scan = jax.jit(la._by_scan)
recurrence = jax.jit(reference.delta_rule)
kernel_gradients = _gradients(la._by_kernels)
recurrence_gradients = _gradients(reference.delta_rule)


def worst(a, b) -> float:
    return float(jnp.abs(a - b).max())


def test_the_forward_kernel_is_the_recurrence_and_the_scan():
    """Over a row that is no whole number of chunks."""
    ops = operands(1)
    o, want = kernels(*ops), recurrence(*ops)
    assert o.shape == want.shape == (1, T, H, D) and o.dtype == jnp.float32
    assert float(jnp.abs(want).max()) > 0.05
    assert worst(o, want) < TOL
    assert worst(o, scan(*ops)) < TOL


@functools.cache
def _both_gradients():
    with jax.default_matmul_precision("highest"):
        ops = operands(201)
        return kernel_gradients(*ops), recurrence_gradients(*ops)


@pytest.mark.parametrize("name", OPERANDS)
def test_the_backward_kernel_is_the_recurrences_gradient(name):
    got, want = _both_gradients()
    i = OPERANDS.index(name)
    assert got[i].shape == want[i].shape and got[i].dtype == jnp.float32
    assert float(jnp.abs(want[i]).max()) > 0.05
    assert worst(got[i], want[i]) < 10 * TOL


@pytest.mark.parametrize("decay", [1.6, 6.0])
def test_the_strong_end_of_the_decay_is_finite_and_the_recurrence(decay):
    """``|g|`` 1.6 a token on every channel (102 over a chunk, where
    float32's ``exp`` ends at 88) and 6 a token (384 over a chunk, 96 over
    a sub-block): every value finite, forward and (at 1.6) gradients the
    recurrence's; then one head that forgets at once beside one that
    never does."""
    q, k, v, g, beta = operands(5, decay=decay)
    ops = (q, k, v, jnp.full_like(g, -decay), beta)
    o = kernels(*ops)
    assert bool(jnp.isfinite(o).all()) and worst(o, recurrence(*ops)) < TOL
    if decay == 1.6:
        for got, want in zip(kernel_gradients(*ops),
                             recurrence_gradients(*ops)):
            assert bool(jnp.isfinite(got).all())
            assert worst(got, want) < 10 * TOL
    ops = (q, k, v, g.at[:, :, 0].set(-decay).at[:, :, 1].set(0.0), beta)
    assert worst(kernels(*ops), recurrence(*ops)) < TOL


def test_no_decay_and_full_steps_on_repeated_keys_stay_exact():
    """The worst case of the triangular inverse: the SAME key at every
    position, ``beta`` = 1, no decay (``I + A`` is all ones below the
    diagonal). The state then holds only the last value."""
    q, k, v, g, beta = operands(7)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    ops = (k, k, v, jnp.zeros_like(g), jnp.ones_like(beta))
    o = kernels(*ops)
    assert worst(o, recurrence(*ops)) < TOL
    assert worst(o, v / math.sqrt(D)) < TOL    # S^T k = the last v


def test_later_tokens_change_no_earlier_output():
    ops = operands(11)
    late = tuple(a.at[:, 150:].set(a[:, 150:] * 0.5) for a in ops)
    early, changed = kernels(*ops), kernels(*late)
    assert worst(early[:, :150], changed[:, :150]) == 0.0
    assert float(jnp.abs(early[:, 150:] - changed[:, 150:]).max()) > 0.0


def test_bfloat16_operands_keep_a_float32_state():
    """The train step's dtypes: bfloat16 q, k, v, float32 g and beta ->
    bfloat16 out within bfloat16's rounding of the float32 recurrence;
    the states a chunk starts from are kept in float32 (and hold more
    than bfloat16 would), the first of them zero."""
    ops = operands(13, t=128)
    half = tuple(a.astype(jnp.bfloat16) for a in ops[:3]) + ops[3:]
    exact = tuple(a.astype(jnp.float32) for a in half)
    flat = lambda a: a.reshape(1, 128, -1)              # noqa: E731
    rows = jnp.transpose(half[4].reshape(1, 2, 64, 1, H), (0, 3, 1, 4, 2))
    o, starts = jax.jit(lambda *a: la._kernel_call(
        la._delta_fwd_kernel, a, [a[2]], starts_out=True, interpret=True))(
            *map(flat, half[:4]), rows)
    want = recurrence(*exact)
    assert o.dtype == jnp.bfloat16 and starts.dtype == jnp.float32
    assert starts.shape == (1, 2, H, D, D)              # [B, chunks, H, dv, dk]
    assert float(jnp.abs(o.reshape(want.shape).astype(jnp.float32)
                         - want).mean()) < 0.02 * float(jnp.abs(want).mean())
    assert float(jnp.abs(starts[:, 0]).max()) == 0.0
    second = starts[:, 1]
    assert float(jnp.abs(second).max()) > 0.05
    assert worst(second, second.astype(jnp.bfloat16).astype(jnp.float32)) > 0.0
    # the second chunk's start is the recurrence's state after 64 tokens
    state = _state_after(exact, 64)                     # [H, dk, dv]
    assert float(jnp.abs(jnp.swapaxes(second[0], 1, 2) - state).mean()) < \
        0.02 * float(jnp.abs(state).mean())


@functools.cache
def _half_gradients(fn):
    """(o, the five gradients) of ``fn`` at [1, 128, 2, 128], jitted once."""
    weight = WEIGHT[:, :128]
    return jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *b: (fn(*b).astype(jnp.float32) * weight).sum(),
        argnums=range(5))(*a)))


@pytest.mark.parametrize("case", ["random", "trained_decay", "repeated_keys"])
def test_bfloat16_is_as_near_the_recurrence_as_the_scan_is(case):
    """The train step's dtypes on the cases the inverse's form is for:
    output and gradients in each operand's own dtype, and no further from
    the float32 recurrence's than the scan's are, at a mild decay, at the
    decay training reaches (4.1 a token: -263 a chunk) and on one key
    repeated at full steps with no decay. ``g``'s gradient is the one that
    tells at the mild decay: without the reference decay's own gradient in
    the backward kernel, the rounding of every below-diagonal product
    reaches ``g`` at all the positions ahead of it (a third more error
    than the scan's); it is left out where the recurrence's is nothing
    but rounding (no decay: the gradient of ``g`` is exactly 0 there)."""
    q, k, v, g, beta = operands(17, t=128)
    if case == "trained_decay":
        g = -4.1 * (0.5 + jax.random.uniform(jax.random.PRNGKey(4), g.shape))
    if case == "repeated_keys":
        q = k = jnp.broadcast_to(k[:, :1], k.shape)
        g, beta = jnp.zeros_like(g), jnp.ones_like(beta)
    half = tuple(a.astype(jnp.bfloat16) for a in (q, k, v)) + (g, beta)
    (o, got), (o_s, by_scan) = (_half_gradients(fn)(*half)
                                for fn in (la._by_kernels, la._by_scan))
    o_r, want = _half_gradients(reference.delta_rule)(
        *(a.astype(jnp.float32) for a in half))
    assert [a.dtype for a in (o, *got)] == [jnp.bfloat16] * 4 + [jnp.float32] * 2
    for name, a, s, r in zip(("o", *OPERANDS), (o, *got), (o_s, *by_scan),
                             (o_r, *want)):
        if (case, name) == ("repeated_keys", "g"):
            continue
        off = lambda x: float(jnp.abs(x.astype(jnp.float32) - r).mean()  # noqa: E731
                              / jnp.abs(r).mean())
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all())
        assert off(a) < 0.03 and off(a) < 1.02 * off(s), (name, off(a), off(s))


def test_a_kernel_is_lowered_once_a_module_however_often_it_is_called():
    """What a train step's set-up time hangs on: the launch is a jitted
    function of its own, so three layers' forward kernels at one shape
    are ONE traced and lowered function called three times (and the
    backward kernels another), not three bodies of ten thousand
    equations each."""
    ops = operands(19, t=128)

    def three_layers(*a):
        q, k, v, g, beta = a
        for _ in range(3):
            v = la._by_kernels(q, k, v, g, beta)
        return v.sum()

    def launches(fn):
        text = jax.jit(fn).lower(*ops).as_text()
        return (re.findall(r"func.func private @(_launch\w*)\(", text),
                re.findall(r"call @(_launch\w*)\(", text))

    bodies, calls = launches(three_layers)
    assert len(bodies) == 1 and len(calls) == 3
    # three forwards that keep the chunk-start states, three backwards: the
    # backward kernel once, the forward at most twice (jax's own
    # bookkeeping may tell the last layer's call from the others')
    bodies, calls = launches(jax.grad(three_layers, argnums=range(5)))
    assert len(calls) == 6 and len(bodies) <= 3
    assert max(calls.count(b) for b in bodies) == 3


def _state_after(ops, n: int):
    """The recurrence's state [H, dk, dv] after ``n`` tokens, by hand."""
    q, k, v, g, beta = (a[0, :n] for a in ops)
    s = jnp.zeros((H, D, D), jnp.float32)
    for t in range(n):
        s = jnp.exp(g[t])[:, :, None] * s
        u = beta[t][:, None] * (v[t] - jnp.einsum("hc,hce->he", k[t], s))
        s = s + k[t][:, :, None] * u[:, None, :]
    return s


class _Device:
    def __init__(self, platform: str):
        self.platform = platform


def _traced(fn, *shapes):
    """``fn`` of tracers of ``shapes``, as a jitted caller sees it."""
    got = []
    jax.eval_shape(lambda *a: got.append(fn(*a)),
                   *(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes))
    return got[0]


@pytest.mark.parametrize("platform,devices,width,taken", [
    ("tpu", 1, 128, True),      # a KDA layer on one chip
    ("tpu", 1, 256, False),     # a width no kernel was measured or sized at
    ("tpu", 1, 16, False),      # the CPU tests' heads: not whole lane tiles
    ("tpu", 1, 192, False),
    ("tpu", 4, 128, False),     # no mesh in force: the jit's devices unseen
    ("cpu", 1, 128, False),     # the interpreter is for these tests only
    ("gpu", 1, 128, False),
])
def test_the_choice_reads_platform_width_and_devices(monkeypatch, platform,
                                                     devices, width, taken):
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Device(platform)] * devices)
    wide, tile = (1, 64, 2, width), (1, 64, 2, 128)
    assert _traced(la._takes_kernels, wide, wide) is taken
    assert _traced(la._takes_kernels, wide, tile) is (taken and width == 128)


def test_the_choice_reads_the_operands_sharding_and_the_mesh_in_force(
        monkeypatch, caplog):
    """What says how many devices lie under the operands, in order: a
    concrete array's sharding, the mesh in force where the call is traced
    (one device, or a ``shard_map`` with every axis manual, leave the
    kernel its whole operand), and only then the process's device count,
    with ONE logged line where that alone keeps the kernels out."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    real = jax.devices()
    assert len(real) == 8
    shape = (1, 64, 2, 128)
    mesh = Mesh(np.array(real).reshape(4, 2), ("a", "b"))
    one = Mesh(np.array(real[:1]), ("a",))
    q = jnp.zeros(shape)
    over = lambda: _traced(la._mesh_over, shape)        # noqa: E731
    # concrete operands: their own sharding, whatever the process holds
    assert la._mesh_over(q) is False and la._mesh_over(np.zeros(shape)) is False
    assert la._mesh_over(jax.device_put(
        q, NamedSharding(mesh, P(None, "a")))) is True
    assert la._mesh_over(jax.device_put(q, NamedSharding(mesh, P()))) is True
    # traced: the mesh in force
    with jax.set_mesh(mesh):
        assert over() is True
    with jax.set_mesh(one):
        assert over() is False
    inside = []
    spec = P(("a", "b"))
    jax.eval_shape(jax.shard_map(
        lambda a: inside.append(la._mesh_over(a)) or a, mesh=mesh,
        in_specs=spec, out_specs=spec), jax.ShapeDtypeStruct((8, 64), "f4"))
    jax.eval_shape(jax.shard_map(
        lambda a: inside.append(la._mesh_over(a)) or a, mesh=mesh,
        in_specs=P("a"), out_specs=P("a"), axis_names={"a"}),
        jax.ShapeDtypeStruct((8, 64), "f4"))
    assert inside == [False, True]      # every axis manual; one left to XLA
    # traced under no mesh: the process's devices, said once
    la._log_once.cache_clear()
    with caplog.at_level("WARNING", logger=la.logger.name):
        assert over() is True and over() is True
    assert len(caplog.records) == 1 and "8 devices" in caplog.text
    monkeypatch.setattr(jax, "devices", lambda *a: real[:1])
    assert over() is False
    # and the whole choice on a TPU that shows four chips: one device's
    # operands, or one device's mesh in force, take the kernels
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device("tpu")] * 4)
    assert la._takes_kernels(q, q) is True
    assert _traced(la._takes_kernels, shape, shape) is False
    with jax.set_mesh(one):
        assert _traced(la._takes_kernels, shape, shape) is True


def test_the_scan_runs_where_the_kernels_do_not_and_they_refuse_a_gpu(
        monkeypatch):
    """On the CPU ``gated_delta_rule`` IS the scan, bit for bit; on a
    platform that is neither a TPU nor the CPU the choice is the scan and
    the kernels themselves refuse (``_interpret``), where interpreting
    would look like a kernel that never finishes."""
    ops = operands(3)
    assert worst(jax.jit(la.gated_delta_rule)(*ops), scan(*ops)) == 0.0
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device("gpu")])
    with pytest.raises(NotImplementedError, match="'gpu'"):
        la._by_kernels(*ops)
