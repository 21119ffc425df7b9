"""A state-space mixer's work along the sequence (Mamba-2, arXiv:2405.21060;
Nemotron-H's ``M`` layers): the LINEAR recurrence ``ops/linear_attention.py``
has, without its delta rule. Per head ``h`` of ``P`` channels, with the
step ``Delta_t`` > 0 and the log-decay ``a_t = Delta_t A`` <= 0 (ONE number
a head and token), the input ``x_t`` in R^P, and ``B_t``, ``C_t`` in R^N
that the ``H / G`` heads of a GROUP share (head ``h`` reads group ``h //
(H / G)``), the state ``S`` in R^{P x N}, ``S_0 = 0``:

    S_t = exp(a_t) S_{t-1} + Delta_t x_t B_t^T
    y_t = S_t C_t + D x_t

Nothing is inverted: a token writes what it is given, so a chunk needs no
triangular solve and every product is a plain matmul. ``ssm_scan`` runs it
in CHUNKS of ``chunk`` positions (the model's ``chunk_size``, 128), ALL
chunks at once, with ``G_t`` the cumulative log-decay inside a chunk:

    M[t, s]  = (C_t . B_s) exp(G_t - G_s)               s <= t, a group's C B^T
                                                        times a head's decays
    Y        = M (Delta x)  +  exp(G) * (C S)           S the chunk's START state
    S'       = exp(G_last) S  +  (exp(G_last - G) Delta x)^T B

**The decay enters only as differences of cumulative log-decays with the
later position first** (``G_t - G_s`` for s <= t, ``G_last - G_s``), masked
BEFORE the exponential: no ``exp`` of a positive sum, whatever the step.
The chunks' own sums (``_chunk_sums``) and outputs (``_chunk_outputs``) are
batched matmuls over every chunk; between them the CARRY walks the chunks
in order, ``T / chunk`` steps of an elementwise update of the [H, P, N]
state (``_carry``, scope ``ssm_carry``): what is bound by latency and not
by the MXU.

**The backward is the op's own** (``jax.custom_vjp``): the forward keeps
the operands and the state at every chunk's START, nothing of a chunk's
[chunk, chunk] matrices. The backward makes the two batched stages again
under ``jax.vjp`` (each is plain arithmetic with no loop) and turns the
carry round by hand (``_carry_back``: it is linear in the state), so what
autodiff ever holds of the intra-chunk arithmetic is that of one stage.

The state and every sum are float32; the products take their operands in
``x``'s dtype (bfloat16 in a train step), as the attention kernels do.

Around the scan, a state-space layer's small parts, in plain XLA under the
scopes the linear mixers' readers read (``linear_attention.SCOPES``):
``step_and_decay`` and ``gated_group_norm`` (``kda_gate``: ``Delta`` and
the decay; the output's RMS norm over GROUPS of channels, wider than a
head, the gate ahead of the norm). The layer's ONE convolution chain on
its flat ``[x | B | C]`` projection is ``linear_attention``'s chain with a
bias and no l2 norm (``linear_attention.flat_conv_silu``, ``kda_conv``).

This file is one of ``models.transformer.SCOPE_FILES``: it opens
``ssm_carry`` and ``kda_gate``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SCOPES = ("ssm_carry",)
_F32 = jnp.float32


def step_and_decay(raw, w):
    """(``Delta`` = softplus(raw + dt_bias), the log-decay ``a`` = -exp(A_log)
    x Delta) [B, T, H] float32 of the step's projection ``raw`` [B, T, H]
    float32 (scope ``kda_gate``). ``w``: ``dt_bias``, ``A_log`` [H]."""
    with jax.named_scope("kda_gate"):
        delta = jax.nn.softplus(raw + w["dt_bias"].astype(_F32))
        return delta, -jnp.exp(w["A_log"].astype(_F32)) * delta


def gated_group_norm(y, z, weight, groups: int, *, eps: float):
    """``rmsnorm_group(y * silu(z)) * weight``: the scan's output ``y`` [B,
    T, H, P] (or flat) times the gate of the FLAT projection ``z`` [B, T,
    C] AHEAD of the norm, the mean square taken over each of ``groups``
    runs of ``C / groups`` channels (wider than a head), ``weight`` [C] a
    channel -> FLAT [B, T, C] in ``z``'s dtype; float32 throughout, rounded
    once (scope ``kda_gate``)."""
    with jax.named_scope("kda_gate"):
        b, t, c = z.shape
        gated = y.reshape(b, t, c).astype(_F32) * jax.nn.silu(z.astype(_F32))
        by_group = gated.reshape(b, t, groups, c // groups)
        normed = by_group * jax.lax.rsqrt(
            jnp.mean(by_group * by_group, -1, keepdims=True) + eps)
        return (normed.reshape(b, t, c) * weight.astype(_F32)).astype(z.dtype)


# -- the chunked scan ------------------------------------------------------------
#
# Inside the op every operand is by chunks: x [B, N, Q, G, R, P] (N chunks
# of Q positions; head h = g R + r), dt and a [B, N, Q, G, R] float32, b and
# c [B, N, Q, G, S].

def _cumulative(a):
    """(the log-decay summed from a chunk's first position to each, the
    whole chunk's) of ``a`` [B, N, Q, G, R]."""
    total = jnp.cumsum(a, axis=2)
    return total, total[:, :, -1]


def _chunk_sums(x, dt, a, b):
    """What a chunk's own tokens add to the state by its END, [B, N, G, R,
    P, S] float32, and the chunk's whole log-decay [B, N, G, R]."""
    total, last = _cumulative(a)
    weight = dt * jnp.exp(last[:, :, None] - total)         # exponent <= 0
    written = (x.astype(_F32) * weight[..., None]).astype(x.dtype)
    return jnp.einsum("bnqgrp,bnqgs->bngrps", written, b,
                      preferred_element_type=_F32), last


def _carry(sums, last):
    """The state at every chunk's START [B, N, G, R, P, S] float32 from
    the chunks' sums and whole log-decays: the sequential part."""
    def chunk(state, ops):
        added, decay = ops
        return jnp.exp(decay)[..., None, None] * state + added, state

    with jax.named_scope("ssm_carry"):
        _, starts = jax.lax.scan(
            chunk, jnp.zeros(sums.shape[:1] + sums.shape[2:], _F32),
            (jnp.moveaxis(sums, 1, 0), jnp.moveaxis(last, 1, 0)))
        return jnp.moveaxis(starts, 0, 1)


def _carry_back(d_starts, starts, last):
    """``_carry`` turned round: the cotangents of its ``sums`` and
    ``last`` from that of the states it returned. ``S_{n+1} = e_n S_n +
    sums_n``: walking from the last chunk, ``lam`` the cotangent of the
    state a chunk hands on."""
    def chunk(lam, ops):
        d_start, start, decay = ops
        e = jnp.exp(decay)
        d_decay = (lam * start).sum((-1, -2)) * e
        return d_start + e[..., None, None] * lam, (lam, d_decay)

    with jax.named_scope("ssm_carry"):
        _, (d_sums, d_last) = jax.lax.scan(
            chunk, jnp.zeros_like(starts[:, 0]),
            tuple(jnp.moveaxis(v, 1, 0) for v in (d_starts, starts, last)),
            reverse=True)
        return jnp.moveaxis(d_sums, 0, 1), jnp.moveaxis(d_last, 0, 1)


def _chunk_outputs(x, dt, a, b, c, starts):
    """The recurrence's output [B, N, Q, G, R, P] float32 from the chunks'
    own tokens and the states they start from."""
    total, _ = _cumulative(a)
    q = a.shape[2]
    # M[t, s] = (C_t . B_s) exp(G_t - G_s), s <= t: masked AHEAD of exp
    by_head = jnp.moveaxis(total, 2, 4)                     # [B, N, G, R, Q]
    later = by_head[..., :, None] - by_head[..., None, :]
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(seen, later, -jnp.inf))
    scores = jnp.einsum("bnqgs,bnkgs->bngqk", c, b,
                        preferred_element_type=_F32)
    mixed = (scores[:, :, :, None] * decay).astype(x.dtype)
    stepped = (x.astype(_F32) * dt[..., None]).astype(x.dtype)
    inside = jnp.einsum("bngrqk,bnkgrp->bnqgrp", mixed, stepped,
                        preferred_element_type=_F32)
    before = jnp.einsum("bnqgs,bngrps->bnqgrp", c, starts.astype(x.dtype),
                        preferred_element_type=_F32)
    return inside + jnp.exp(total)[..., None] * before


@jax.custom_vjp
def _scan(x, dt, a, b, c):
    return _scan_fwd(x, dt, a, b, c)[0]


def _scan_fwd(x, dt, a, b, c):
    sums, last = _chunk_sums(x, dt, a, b)
    starts = _carry(sums, last)
    y = _chunk_outputs(x, dt, a, b, c, starts).astype(x.dtype)
    return y, (x, dt, a, b, c, starts)


def _scan_bwd(kept, d_y):
    x, dt, a, b, c, starts = kept
    _, outputs_back = jax.vjp(_chunk_outputs, x, dt, a, b, c, starts)
    dx, d_dt, da, db, dc, d_starts = outputs_back(d_y.astype(_F32))
    (_, last), sums_back = jax.vjp(_chunk_sums, x, dt, a, b)
    more = sums_back(_carry_back(d_starts, starts, last))
    dx, d_dt, da, db = (one + two for one, two
                        in zip((dx, d_dt, da, db), more))
    return dx, d_dt, da, db, dc


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssm_scan(x, dt, a, b, c, skip, *, chunk: int):
    """The state-space recurrence in chunks (module docstring): ``x`` [B,
    T, H, P], ``dt`` (the step ``Delta``) and ``a`` (the log-decay, <= 0)
    [B, T, H] float32, ``b`` and ``c`` [B, T, G, S] (head ``h`` reads group
    ``h // (H / G)``), ``skip`` [H] (``D``) -> ``y`` [B, T, H, P] in
    ``x``'s dtype, ``S_0 = 0``. A ``T`` that is no whole number of chunks
    is padded behind the row with tokens that write nothing (``dt`` = 0)
    and forget nothing (``a`` = 0). Differentiable in all six operands."""
    bsz, t, h, p = x.shape
    g = b.shape[2]
    pad = -t % chunk

    def by_chunks(v, *tail):
        if pad:
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape(bsz, (t + pad) // chunk, chunk, *tail)

    y = _scan(by_chunks(x, g, h // g, p), by_chunks(dt.astype(_F32), g, h // g),
              by_chunks(a.astype(_F32), g, h // g),
              by_chunks(b, g, b.shape[3]), by_chunks(c, g, c.shape[3]))
    y = y.reshape(bsz, t + pad, h, p)[:, :t]
    return (y.astype(_F32) + skip.astype(_F32)[:, None] * x.astype(_F32)
            ).astype(x.dtype)
