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

**Two forms of the same arithmetic, and ONE rule between them**
(``_takes_kernels``, from what a call shows: platform, mesh, shapes):

- **On one TPU chip, at the widths the kernels were measured at** (chunk
  and state 128, a group's heads a whole number of 128-lane tiles): two
  Pallas kernels, ``_ssm_fwd_kernel`` and ``_ssm_bwd_kernel``, over a
  grid (batch, groups, chunks) whose chunk axis is sequential. A chunk's
  [chunk, chunk] decay matrices, its scores ``C B^T`` (once a group) and
  ``M`` never leave VMEM, and the [state, heads x channels] state rides
  the chunk walk in a VMEM scratch: there is no separate carry, and no
  instruction under ``ssm_carry``. The backward walks the same grid from
  the last chunk with the state's cotangent in the scratch.
- **Everywhere else** (the CPU, a mesh over the operand, other widths),
  and as the tests' second opinion: plain XLA. The chunks' own sums
  (``_chunk_sums``) and outputs (``_chunk_outputs``) are batched matmuls
  over every chunk; between them the CARRY walks the chunks in order,
  ``T / chunk`` steps of an elementwise update of the [H, P, N] state
  (``_carry``, scope ``ssm_carry``). Its backward makes the two batched
  stages again under ``jax.vjp`` (each is plain arithmetic with no loop)
  and turns the carry round by hand (``_carry_back``: it is linear in the
  state).

**The backward is the op's own in both** (``jax.custom_vjp``): the forward
keeps the operands and the state at every chunk's START (the same bytes in
both forms), nothing of a chunk's [chunk, chunk] matrices.

The state and every sum are float32; the products take their operands in
``x``'s dtype (bfloat16 in a train step), as the attention kernels do.

Around the scan, a state-space layer's other parts, under the scopes the
linear mixers' readers read (``linear_attention.SCOPES``):
``step_and_decay`` (``kda_gate``: ``Delta`` and the decay, [B, T, H]
numbers in plain XLA) and the two passes over the layer's wide arrays,
which on one TPU chip are the Pallas passes the KDA / Gated DeltaNet
mixers have, on the same flat tiling the scan's kernels read and write,
so that between the input projections and ``wo`` no [B, T, heads x
channels] array is written in float32 or changes its tiling:
``gated_group_norm`` (``kda_gate``: the output's RMS norm over GROUPS of
channels, wider than a head, the gate ahead of the norm, a weight a
channel: ``linear_attention._norm_fwd_kernel`` / ``_norm_bwd_kernel`` with
a ``group``, where ``_norm_takes_kernels`` finds their case) and the
layer's ONE convolution chain on its flat ``[x | B | C]`` projection,
``linear_attention``'s chain with a bias row and no l2 norm
(``linear_attention.flat_conv_silu``, ``kda_conv``). Each of the three ops
asks ``linear_attention._one_tpu`` (a TPU, no mesh over the operand) and
its own shapes; everywhere else its plain form runs.

This file is one of ``models.transformer.SCOPE_FILES``: it opens
``ssm_carry`` (the plain form alone) and ``kda_gate``.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp

from ray_tpu.ops import linear_attention as la
from ray_tpu.ops.linear_attention import _NT, _TN, _iota, _mm

SCOPES = ("ssm_carry",)
_F32 = jnp.float32


def step_and_decay(raw, w):
    """(``Delta`` = softplus(raw + dt_bias), the log-decay ``a`` = -exp(A_log)
    x Delta) [B, T, H] float32 of the step's projection ``raw`` [B, T, H]
    float32 (scope ``kda_gate``). ``w``: ``dt_bias``, ``A_log`` [H]. With
    ``A_log`` [channels, state] (Mamba-1: a decay a channel AND state index,
    which ``selective_scan`` multiplies out itself) the second is ``A`` =
    -exp(A_log) as it is."""
    with jax.named_scope("kda_gate"):
        delta = jax.nn.softplus(raw + w["dt_bias"].astype(_F32))
        a = -jnp.exp(w["A_log"].astype(_F32))
        return delta, (a * delta if a.ndim == 1 else a)


def gated_group_norm(y, z, weight, groups: int, *, eps: float):
    """``rmsnorm_group(y * silu(z)) * weight``: the scan's output ``y`` [B,
    T, H, P] (or flat) times the gate of the FLAT projection ``z`` [B, T,
    C] AHEAD of the norm, the mean square taken over each of ``groups``
    runs of ``C / groups`` channels (wider than a head), ``weight`` [C] a
    channel -> FLAT [B, T, C] in ``z``'s dtype; float32 throughout, rounded
    once (scope ``kda_gate``). Where ``_norm_takes_kernels`` finds their
    case ONE Pallas pass forward and one backward over the flat arrays
    (the linear mixers' head norm's kernels, handed the group's lanes),
    else ``_group_norm_plain``, which is what they are tested against."""
    with jax.named_scope("kda_gate"):
        y, group = y.reshape(z.shape), z.shape[2] // groups
        if _norm_takes_kernels(y, group):
            return la._head_norm_kernels(y, (z,), weight, eps, group)
        return _group_norm_plain(y, z, weight, groups, eps)


def _group_norm_plain(y, z, weight, groups: int, eps: float):
    """``gated_group_norm`` in plain XLA: the flat arrays viewed by groups
    for the mean."""
    b, t, c = z.shape
    gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    by_group = gated.reshape(b, t, groups, c // groups)
    normed = by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, -1, keepdims=True) + eps)
    return (normed.reshape(b, t, c) * weight.astype(_F32)).astype(z.dtype)


def _norm_takes_kernels(y, group: int) -> bool:
    """Whether the norm's Pallas kernels run a call, from what can be seen
    of it (``y`` [B, T, C] flat, ``group`` lanes a statistic): one TPU chip
    under ``y`` (``linear_attention._one_tpu``); a group a whole number of
    128-lane tiles that divides a grid step's lanes, so a step holds its
    groups whole."""
    return (group % _LANES == 0 and la._conv_tile(y)[1] % group == 0
            and la._one_tpu(y))


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


# -- the same chunk walk, as Pallas kernels -----------------------------------------
#
# One grid step is one chunk of ONE GROUP's heads, grid (batch, groups,
# chunks), the chunks sequential (``ops/linear_attention.py``'s kernels are
# the pattern). The operands arrive FLAT, ``x`` [B, T, H * P], ``b`` and
# ``c`` [B, T, G * S], ``dt`` and ``a`` [B, T, H] as they are: a group is a
# run of whole 128-lane tiles of ``x`` and one tile of ``b`` and ``c``, and
# a step reads every head's ``dt`` and ``a`` (H lanes) and picks its own.
# A chunk's [Q, Q] decay matrices, its scores and ``mixed`` live in VMEM
# and nowhere else; the state, float32 and TRANSPOSED ([S, heads x
# channels]: a head's decay scales its lanes, and every product with it is
# one the MXU takes as it stands), is a scratch that the chunk axis carries.
#
# **Numbers a head and token reach the lanes by 0/1 matrices on the MXU**
# (``_picked``): the cumulative log-decay [Q, H] is a triangle of ones
# against ``a``, and a head's column is SPREAD over its P lanes ([Q, L])
# and laid as a ROW ([heads, Q]) by one-hot products, the float32 split
# into three bfloat16 parts that sum to it, so the row and the column of
# ``G_t - G_s`` are the SAME float32 and the diagonal's exponent is 0
# exactly. Two heads of 64 channels share a 128-lane tile: ``M X`` runs a
# tile at a time, each head's ``M`` against the whole tile and a select
# after (the MXU is 128 columns wide either way), and nothing is sliced
# inside a tile.

_LANES = 128    # the chunk, the state and a tile of lanes: what was measured


def _thirds(x):
    """float32 -> three bfloat16 parts that sum to it."""
    high = x.astype(jnp.bfloat16)
    return (high, *la._split(x - high.astype(_F32)))


def _picked(x, ones, dims=((1,), (0,)), ones_first: bool = False):
    """``x`` (float32) against the 0/1 matrix ``ones`` (``ones_first``:
    ``ones`` against ``x``), every product exact whatever precision the
    MXU gives float32 operands; a one-hot ``ones`` moves ``x``'s numbers
    as they are."""
    return sum(_mm(ones, part, dims) if ones_first else _mm(part, ones, dims)
               for part in _thirds(x))


def _ones(mask):
    """The 0/1 matrix of ``mask``, bfloat16."""
    return jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)


def _kernel_chunk(x_ref, dt_ref, a_ref, b_ref, c_ref, p: int):
    """A step's operands and what both kernels make of them first, [Q, L]
    arrays over the group's L = heads x ``p`` lanes unless said: ``x``,
    ``b``, ``c`` as read, ``xf`` float32; ``g`` the cumulative log-decay,
    ``g_rows`` the same a head a ROW ([>= heads, Q]); ``step`` (Delta);
    ``grown`` exp(G), ``tail`` exp(G_last - G), ``gamma`` exp(G_last) [1,
    L]; ``weight`` Delta x tail; ``stepped`` and ``written`` (x Delta, x
    weight, rounded as ``_chunk_outputs`` and ``_chunk_sums`` round them);
    ``scores`` C B^T [Q, Q] float32, once a group; ``decay(h)`` head h's
    exp(G_t - G_s), masked AHEAD of the exponential."""
    import jax.experimental.pallas as pl

    k = types.SimpleNamespace(x=x_ref[0], b=b_ref[0], c=c_ref[0])
    k.dt, k.xf = k.x.dtype, k.x.astype(_F32)
    (q, lanes), heads = k.x.shape, dt_ref.shape[-1]
    r = lanes // p
    first = pl.program_id(1) * r                    # the group's first head
    seen = _iota((q, q), 0) >= _iota((q, q), 1)
    k.lower = _ones(seen)
    rows = -(-r // 16) * 16
    spread = _ones(_iota((heads, lanes), 0)
                   == first + _iota((heads, lanes), 1) // p)
    row, head = _iota((rows, heads), 0), _iota((rows, heads), 1)
    total = _picked(a_ref[0], k.lower, ones_first=True)         # [Q, H]
    k.g = _picked(total, spread)
    k.g_rows = _picked(total, _ones((head == first + row) & (row < r)), _NT,
                       ones_first=True)
    k.step = _picked(dt_ref[0], spread)
    last = k.g[q - 1:q]
    k.grown, k.tail, k.gamma = jnp.exp(k.g), jnp.exp(last - k.g), jnp.exp(last)
    k.weight = k.step * k.tail
    k.stepped = (k.xf * k.step).astype(k.dt)
    k.written = (k.xf * k.weight).astype(k.dt)
    k.scores = _mm(k.c, k.b, _NT)
    k.decay = lambda h: jnp.exp(jnp.where(
        seen, k.g[:, h * p:h * p + 1] - k.g_rows[h:h + 1], -jnp.inf))
    return k


def _tiles(lanes: int, p: int):
    """(a tile's lanes, [(its i-th head, that head's lanes of the tile)])
    for every 128-lane tile of a group's ``lanes``."""
    per = _LANES // p
    of = _iota((_LANES, _LANES), 1) // p
    return [(slice(t * _LANES, (t + 1) * _LANES),
             [(t * per + i, of == i) for i in range(per)])
            for t in range(lanes // _LANES)]


def _by_head(parts):
    """One [Q, 128] tile from ``parts``, [(its lanes' mask, the array whose
    lanes under the mask are the head's)]."""
    out = parts[0][1]
    for mask, one in parts[1:]:
        out = jnp.where(mask, one, out)
    return out


def _ssm_fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, *rest, p: int):
    import jax.experimental.pallas as pl

    *starts_ref, state = rest       # the chunk-start states only if kept

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    k = _kernel_chunk(x_ref, dt_ref, a_ref, b_ref, c_ref, p)
    s = state[...]
    if starts_ref:
        starts_ref[0][0, 0] = s
    before = _mm(k.c, s.astype(k.dt))                           # C S
    for cols, heads in _tiles(k.x.shape[1], p):
        inside = _by_head([
            (mask, _mm((k.scores * k.decay(h)).astype(k.dt),
                       k.stepped[:, cols])) for h, mask in heads])
        y_ref[0, :, cols] = (inside + k.grown[:, cols] * before[:, cols]
                             ).astype(y_ref.dtype)
    state[...] = k.gamma * s + _mm(k.b, k.written, _TN)


def _ssm_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, starts_ref, dy_ref,
                    dx_ref, ddt_ref, da_ref, db_ref, dc_ref, d_state, *,
                    p: int):
    """The forward's step turned round, ``d_state`` the cotangent of the
    state a chunk hands on. A cotangent enters a product rounded to the
    operands' dtype, as a TPU's default precision rounds it in the plain
    form's gradient. ``d dt`` and ``d a`` leave as ROWS, [heads, Q]: [.., Q,
    heads] would be 16 times its size in HBM's tiles."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        d_state[...] = jnp.zeros_like(d_state)

    k = _kernel_chunk(x_ref, dt_ref, a_ref, b_ref, c_ref, p)
    q, lanes = k.x.shape
    r = lanes // p
    rows = k.g_rows.shape[0]
    s, d_after, dy = starts_ref[0, 0], d_state[...], dy_ref[0]
    sd, dsd = s.astype(k.dt), d_after.astype(k.dt)
    before = _mm(k.c, sd)
    lit = k.grown * dy.astype(_F32)                 # d (C S)
    litd = lit.astype(k.dt)
    d_written = _mm(k.b, dsd)
    # what the cumulative log-decay receives over the lanes: through
    # ``grown``, and the chunk's whole decay (through ``tail`` and
    # ``gamma``) on the last position's
    moved = d_written * k.xf
    kept = moved * k.weight
    d_last = (jnp.sum(kept, 0, keepdims=True)
              + k.gamma * jnp.sum(d_after * s, 0, keepdims=True))
    d_g = lit * before - kept + jnp.where(
        _iota((q, lanes), 0) == q - 1, d_last, 0.0)
    # the chunk's own matrices, a head at a time: d M = d_y X^T, d X = M^T
    # d_y; the decay's cotangent along a token's row less down its column
    d_scores = jnp.zeros((q, q), _F32)
    d_g_rows = jnp.zeros((rows, q), _F32)
    d_g_cols = jnp.zeros((q, _LANES), _F32)
    at_row, at_col = _iota(d_g_rows.shape, 0), _iota(d_g_cols.shape, 1)
    d_stepped, d_step = [], []
    for cols, heads in _tiles(lanes, p):
        dy_t, parts = dy[:, cols], []
        for h, mask in heads:
            decay = k.decay(h)
            mixed = k.scores * decay
            d_mixed = _mm(jnp.where(mask, dy_t, jnp.zeros_like(dy_t)),
                          k.stepped[:, cols], _NT)
            parts.append((mask, _mm(mixed.astype(k.dt), dy_t, _TN)))
            d_scores = d_scores + d_mixed * decay
            through = d_mixed * mixed
            d_g_cols = jnp.where(
                at_col == h, jnp.sum(through, 1, keepdims=True), d_g_cols)
            d_g_rows = jnp.where(
                at_row == h, -jnp.sum(through, 0, keepdims=True), d_g_rows)
        d_stepped.append(_by_head(parts))
        d_step.append(d_stepped[-1] * k.xf[:, cols]
                      + moved[:, cols] * k.tail[:, cols])
    d_stepped = jnp.concatenate(d_stepped, 1)
    d_scores = d_scores.astype(k.dt)
    dc_ref[0] = (_mm(d_scores, k.b) + _mm(litd, sd, _NT)).astype(dc_ref.dtype)
    db_ref[0] = (_mm(d_scores, k.c, _TN) + _mm(k.written, dsd, _NT)
                 ).astype(db_ref.dtype)
    d_state[...] = k.gamma * d_after + _mm(k.c, litd, _TN)
    dx_ref[0] = (d_stepped * k.step + d_written * k.weight
                 ).astype(dx_ref.dtype)
    # summed over a head's lanes and laid as rows; ``a``'s is the running
    # sum UP the chunk of the cumulative log-decay's
    gather = _ones(_iota((rows, lanes), 0) == _iota((rows, lanes), 1) // p)
    d_g_rows = (d_g_rows + d_g_cols.T[:rows]
                + _picked(d_g, gather, _NT, ones_first=True))
    da_ref[0, 0, 0] = _picked(d_g_rows, k.lower)[:r]
    ddt_ref[0, 0, 0] = _picked(jnp.concatenate(d_step, 1), gather, _NT,
                               ones_first=True)[:r]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _launch(backward: bool, keep: bool, interpret: bool, *operands):
    """One ``pallas_call`` over (batch, groups, chunks), the chunks
    sequential and, ``backward``, walked from the last; ``keep`` adds the
    chunk-start states [B, T / Q, S, H * P] float32 to the forward's
    output. Behind a ``jax.jit`` of its own so that a kernel's body is
    traced once a process (``linear_attention._launch``)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ray_tpu.ops.attention import _FLASH_VMEM_MOST

    x, dt, _, b = operands[:4]
    (bsz, t, flat), h, q, s = x.shape, dt.shape[-1], _LANES, _LANES
    g, n = b.shape[-1] // s, t // q
    lanes, r = flat // g, h // g
    at = (lambda j: n - 1 - j) if backward else (lambda j: j)
    by_group = lambda width: pl.BlockSpec(
        (1, q, width), lambda i, gi, j: (i, at(j), gi))
    by_token = pl.BlockSpec((1, q, h), lambda i, gi, j: (i, at(j), 0))
    states = pl.BlockSpec((1, 1, s, lanes), lambda i, gi, j: (i, at(j), 0, gi))
    rows = pl.BlockSpec((1, 1, 1, r, q), lambda i, gi, j: (i, gi, at(j), 0, 0))
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    in_specs = [by_group(lanes), by_token, by_token, by_group(s), by_group(s)]
    if backward:
        in_specs += [states, by_group(lanes)]
        out_specs = [by_group(lanes), rows, rows, by_group(s), by_group(s)]
        as_rows = jax.ShapeDtypeStruct((bsz, g, n, r, q), _F32)
        out_shape = [like(x), as_rows, as_rows, like(b), like(b)]
    else:
        out_specs = [by_group(lanes)] + [states] * keep
        out_shape = [like(x)] + [
            jax.ShapeDtypeStruct((bsz, n, s, flat), _F32)] * keep
    return pl.pallas_call(
        functools.partial(_ssm_bwd_kernel if backward else _ssm_fwd_kernel,
                          p=flat // h),
        grid=(bsz, g, n), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((s, lanes), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM_MOST),
        interpret=interpret,
    )(*operands)


def _kernel_call(backward: bool, keep: bool, *operands):
    from ray_tpu.ops.attention import _interpret

    return _launch(backward, keep, _interpret(), *operands)


@jax.custom_vjp
def _kernel_scan(x, dt, a, b, c):
    """``_scan`` through the kernels: ``x`` [B, T, H * P], ``dt`` and ``a``
    [B, T, H] float32, ``b`` and ``c`` [B, T, G * S], T whole chunks -> y
    [B, T, H * P]."""
    return _kernel_call(False, False, x, dt, a, b, c)[0]


def _kernel_scan_fwd(x, dt, a, b, c):
    y, starts = _kernel_call(False, True, x, dt, a, b, c)
    return y, (x, dt, a, b, c, starts)


def _kernel_scan_bwd(kept, d_y):
    dx, d_dt, da, db, dc = _kernel_call(True, False, *kept, d_y)
    by_token = lambda rows: jnp.transpose(rows, (0, 2, 4, 1, 3)).reshape(
        kept[1].shape)
    return dx, by_token(d_dt), by_token(da), db, dc


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def _takes_kernels(x, b, chunk: int) -> bool:
    """Whether the Pallas kernels run a call, from what can be seen of it
    (``x`` [B, T, H, P], ``b`` [B, T, G, S]): a TPU; the shapes the kernels
    were written and measured at (a chunk and a state of 128, heads that
    fill 128 lanes a whole number at a time, a group a whole number of such
    tiles and its heads' rows inside one); no mesh over the operand
    (``linear_attention._one_tpu``). Everything else is the plain form's."""
    (h, p), (g, s) = x.shape[2:], b.shape[2:]
    return (chunk == s == _LANES and _LANES % p == 0
            and (h // g * p) % _LANES == 0 and h // g <= _LANES
            and la._one_tpu(x))


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

    def padded(v):
        if pad:
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v

    if _takes_kernels(x, b, chunk):
        # flat all the way: [.., H, P] and [.., H * P] are two layouts on a
        # TPU, and the mixer hands over, and takes back, the flat one
        flat = lambda v: v.reshape(bsz, v.shape[1], -1)
        x = flat(x)
        y = _kernel_scan(padded(x), padded(dt.astype(_F32)),
                         padded(a.astype(_F32)), padded(flat(b)),
                         padded(flat(c)))[:, :t]
        skip = jnp.repeat(skip.astype(_F32), p)
    else:
        by_chunks = lambda v, *tail: padded(v).reshape(
            bsz, (t + pad) // chunk, chunk, *tail)
        y = _scan(by_chunks(x, g, h // g, p),
                  by_chunks(dt.astype(_F32), g, h // g),
                  by_chunks(a.astype(_F32), g, h // g),
                  by_chunks(b, g, b.shape[3]), by_chunks(c, g, c.shape[3]))
        y = y.reshape(bsz, t + pad, h, p)[:, :t]
        skip = skip.astype(_F32)[:, None]
    return (y.astype(_F32) + skip * x.astype(_F32)).astype(x.dtype).reshape(
        bsz, t, h, p)


# -- the selective scan (Mamba-1): a decay a channel AND a state index --------------
#
# ``H_t[c, n] = exp(Delta_t[c] A[c, n]) H_{t-1}[c, n] + Delta_t[c] x_t[c] B_t[n]``,
# ``y_t[c] = sum_n H_t[c, n] C_t[n] + D[c] x_t[c]``: the decay is an array
# [channels, state] a token, ``B_t`` and ``C_t`` are every channel's, and
# there is no matrix product in it (``ssm_scan``'s chunks are matmuls
# because a head has ONE decay). In plain XLA, in chunks of ``chunk``
# positions, the state [.., state, channels] float32 with the CHANNELS in
# the lanes:
#
#   starts   every chunk's START state: what a chunk's own tokens leave at
#            its end is ONE fused sum over its positions (``exp(A (G_last -
#            G_t))``, exponent <= 0), and a short scan over the chunks
#            carries it (``_selective_starts``);
#   the walk ALL chunks step through their positions in lockstep, one
#            ``lax.scan`` step a position on [B, T / chunk, state,
#            channels], each from its own start state (``_walk``); its
#            output stays float32 until ``D x`` is added: rounded ONCE.
#
# [T, channels, state] float32 (5.4 GB a layer at 16,384 tokens and 5,120
# channels) never exists: the residuals are the operands and the start
# states (42 MB). The backward is the op's own: the chunks' start states'
# cotangents the same way round (a fused sum, a short scan from the last
# chunk), then the walk again forward (the states made anew: ``d C`` and
# what ``H_t`` gives the decay's gradient) and once backward (the states'
# cotangents: ``d x``, ``d B``, the rest of the decay's). The decay's
# gradient needs ``lambda_t * H_{t-1}`` where the two walks run opposite
# ways; it is taken as the running sum ``W_t = sum_{s >= t} (g_s H_s -
# lambda_s u_s)`` inside a chunk plus ``d S_end * S_end`` at its end (``W_t
# = lambda_t alpha_t H_{t-1}``, ``lambda_t H_t = g_t H_t + W_{t+1}``), so
# nothing is ever divided by a decay. Every exponent is ``Delta A`` or a
# difference of cumulative sums with the later position first, <= 0.

SELECTIVE_CHUNK = 128
# Positions a ``while`` step of the walk holds. On the v5e at [1, 16384, 5120]
# 1, 2, 4, 8 read 53.9, 50.7, 49.3, 55.6 ms forward + backward (PERF.md, PR
# 62): 4 is 9% of the scan and 0.8 GB of the step's memory, which the
# Phi-4-mini-flash cell does not have.
_WALK_UNROLL = 1


def _in_chunks(v, q: int):
    """[B, T, ...] -> [B, T / Q, Q, ...] float32."""
    return v.reshape(v.shape[0], -1, q, *v.shape[2:]).astype(_F32)


def _chunked(v, q: int):
    """[B, T, ...] -> [Q, B, T / Q, ...]: a chunk's positions first."""
    return jnp.moveaxis(v.reshape(v.shape[0], -1, q, *v.shape[2:]), 2, 0)


def _advance(h, x_t, d_t, b_t, a):
    """One position of the recurrence on every chunk's state ``h`` [B, N,
    S, C]: ``exp(Delta_t A) h + (Delta_t x_t) (x) B_t``."""
    return (jnp.exp(d_t[:, :, None] * a) * h
            + (d_t * x_t)[:, :, None] * b_t[..., None])


def _unchunked(v):
    """[Q, B, N, ...] -> [B, N * Q, ...]."""
    v = jnp.moveaxis(v, 0, 2)
    return v.reshape(v.shape[0], -1, *v.shape[3:])


def _chunk_decays(delta, q: int):
    """(the step summed from a chunk's first position to each, [B, N, Q,
    C], the whole chunk's [B, N, C]) of ``delta`` [B, T, C]."""
    total = jnp.cumsum(_in_chunks(delta, q), axis=2)
    return total, total[:, :, -1]


def _carry_chunks(added, whole, reverse: bool = False):
    """``S' = whole * S + added`` along the chunks (axis 1) from zero ->
    the state each chunk is HANDED ([B, N, S, C]: its start state, or with
    ``reverse`` the cotangent of its end state)."""
    def chunk(state, ops):
        add, keep = ops
        return keep * state + add, state

    with jax.named_scope("ssm_carry"):
        _, handed = jax.lax.scan(
            chunk, jnp.zeros_like(added[:, 0]),
            (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)),
            reverse=reverse)
        return jnp.moveaxis(handed, 0, 1)


def _selective_starts(x, delta, a, b, q: int):
    """Every chunk's start state [B, N, S, C] float32."""
    total, last = _chunk_decays(delta, q)
    written = _in_chunks(x, q) * _in_chunks(delta, q)
    left = jnp.sum(
        jnp.exp((last[:, :, None] - total)[:, :, :, None] * a)
        * written[:, :, :, None] * _in_chunks(b, q)[..., None], axis=2)
    return _carry_chunks(left, jnp.exp(last[:, :, None] * a))


def _walk(x, delta, a, b, c, starts, q: int):
    """y [B, T, C] float32 (without ``D x``): every chunk from its start
    state, position by position, all chunks at once."""
    def position(h, ops):
        x_t, d_t, b_t, c_t = (v.astype(_F32) for v in ops)
        h = _advance(h, x_t, d_t, b_t, a)
        return h, jnp.sum(h * c_t[..., None], axis=2)

    _, y = jax.lax.scan(position, starts,
                        tuple(_chunked(v, q) for v in (x, delta, b, c)),
                        unroll=_WALK_UNROLL)
    return _unchunked(y)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _selective(x, delta, a, b, c, q):
    return _selective_fwd(x, delta, a, b, c, q)[0]


def _selective_fwd(x, delta, a, b, c, q):
    starts = _selective_starts(x, delta, a, b, q)
    return _walk(x, delta, a, b, c, starts, q), (x, delta, a, b, c, starts)


def _selective_bwd(q, kept, d_y):
    x, delta, a, b, c, starts = kept
    total, last = _chunk_decays(delta, q)
    # the cotangent of every chunk's END state: what its later chunks read
    read = jnp.sum(jnp.exp(total[:, :, :, None] * a)
                   * _in_chunks(d_y, q)[:, :, :, None]
                   * _in_chunks(c, q)[..., None], axis=2)
    d_ends = _carry_chunks(read, jnp.exp(last[:, :, None] * a), reverse=True)
    ends = jnp.concatenate([starts[:, 1:], jnp.zeros_like(starts[:, :1])], 1)
    at_end = d_ends * ends                                  # W past a chunk
    ops = tuple(_chunked(v, q) for v in (x, delta, b, c, d_y))
    ops += (jnp.moveaxis(total, 2, 0),)
    zero = jnp.zeros(a.shape, _F32)

    def forth(carry, ops):
        """The states again: ``d C`` and ``g_t H_t``'s two sums."""
        h, d_a = carry
        x_t, d_t, b_t, c_t, g_t, sum_t = (v.astype(_F32) for v in ops)
        h = _advance(h, x_t, d_t, b_t, a)
        lit = h * g_t[:, :, None]                           # H_t dy_t
        through = lit * c_t[..., None]                      # g_t H_t
        d_a = d_a + jnp.sum(through * sum_t[:, :, None], (0, 1))
        return (h, d_a), (jnp.sum(lit, 3), jnp.sum(through * a, 2))

    def back(carry, ops):
        """The states' cotangents: ``d x``, ``d B``, ``lambda_t u_t``'s
        two sums; ``ahead`` is what a state's successor hands it."""
        ahead, d_a = carry
        x_t, d_t, b_t, c_t, g_t, sum_t = (v.astype(_F32) for v in ops)
        lam = c_t[..., None] * g_t[:, :, None] + ahead
        wrote = d_t * x_t
        aimed = lam * b_t[..., None]                        # lambda_t B_t
        d_wrote = jnp.sum(aimed, 2)
        through = aimed * wrote[:, :, None]                 # lambda_t u_t
        d_a = d_a + jnp.sum(through * sum_t[:, :, None], (0, 1))
        out = (d_wrote * d_t, d_wrote * x_t,
               jnp.sum(lam * wrote[:, :, None], 3), jnp.sum(through * a, 2))
        return (jnp.exp(d_t[:, :, None] * a) * lam, d_a), out

    (_, d_a_forth), (d_c, gained) = jax.lax.scan(
        forth, (starts, zero), ops, unroll=_WALK_UNROLL)
    (_, d_a_back), (d_x, d_delta, d_b, lost) = jax.lax.scan(
        back, (d_ends, zero), ops, reverse=True, unroll=_WALK_UNROLL)
    # W_t summed over the state index against A: the running sum from a
    # chunk's last position, and what lies past the chunk
    inside = jnp.flip(jnp.cumsum(jnp.flip(gained - lost, 0), 0), 0)
    d_delta = _unchunked(d_delta + inside) + jnp.repeat(
        jnp.sum(at_end * a, 2), q, axis=1)
    d_a = d_a_forth - d_a_back + jnp.sum(at_end * last[:, :, None], (0, 1))
    return (_unchunked(d_x).astype(x.dtype), d_delta.astype(delta.dtype),
            d_a.astype(a.dtype), _unchunked(d_b).astype(b.dtype),
            _unchunked(d_c).astype(c.dtype))


_selective.defvjp(_selective_fwd, _selective_bwd)


def selective_scan(x, delta, a, b, c, skip, *, chunk: int = SELECTIVE_CHUNK):
    """The Mamba-1 recurrence (the comment above): ``x`` [B, T, C], the
    step ``delta`` [B, T, C] float32 (> 0), ``a`` [C, N] (``A``, < 0),
    ``b`` and ``c`` [B, T, N] (every channel's), ``skip`` [C] (``D``) ->
    ``y`` [B, T, C] in ``x``'s dtype, ``H_0 = 0``. A ``T`` that is no whole
    number of chunks is padded behind the row with tokens that write
    nothing and forget nothing (``delta`` = 0). Differentiable in all six
    operands; the state, the decays and every sum float32."""
    t = x.shape[1]
    q = min(chunk, t)
    pad = -t % q
    padded = lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v
    y = _selective(padded(x), padded(delta.astype(_F32)),
                   a.astype(_F32).T, padded(b), padded(c), q)[:, :t]
    return (y + skip.astype(_F32) * x.astype(_F32)).astype(x.dtype)
