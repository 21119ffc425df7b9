"""Linear attention that carries a STATE along the sequence: the causal
depthwise short convolution and the gated delta rule of Kimi Delta
Attention (KDA; Kimi Linear, arXiv:2510.26692), the first ops here whose
work is a recurrence over positions and not a sum over (query, key) pairs.

Per head, with keys ``k_t`` and queries ``q_t`` in R^dk (the caller's
l2-normed ones), values ``v_t`` in R^dv, a log-decay per CHANNEL ``g_t``
<= 0 in R^dk and a step ``beta_t`` in (0, 1), the state ``S`` in R^{dk x
dv}, ``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(dk)

Token by token that is T sequential steps. ``gated_delta_rule`` runs it
in CHUNKS of ``CHUNK`` positions, one ``lax.scan`` step a chunk over all
heads at once: what the chunk needs of its own tokens (``_intra``: matrix
products) and then four products with the state it starts from
(``_chunk_forward``). With ``G_r`` the cumulative log-decay
inside the chunk, ``u_t = beta_t (v_t - (diag(exp g_t) S_{t-1})^T k_t)``
the value a token really writes, and the chunk's starting state ``S``:

    A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])         s <  t
    B[t, s] = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])         s <= t
    T = (I + diag(beta) A)^-1                                  unit lower triangular
    U = T diag(beta) (V - (exp(G) * K) S)  = U0 - W S
    O = ((exp(G) * Q) S + B U) / sqrt(dk)
    S' = exp(G_last) * S + (exp(G_last - G) * K)^T U

**The decay enters only as differences of cumulative log-gates with the
later position first**, never as ``exp(-G)``: a head's ``|g|`` reaches 1.6
a token at the model's own init, 100 over a chunk, and float32 ends at
88. ``A`` and ``B`` are made in sub-blocks of ``SUB`` positions: a block
BELOW the diagonal as one product of rows scaled by ``exp(G_t - R)`` and
columns scaled by ``exp(R - G_s)``, ``R`` the cumulative log-decay just
ahead of the row block (both exponents <= 0); a block ON the diagonal
from ``exp(G_t - G_s)`` itself, position pair by position pair. The
triangular inverse is forward substitution in the diagonal sub-blocks
and block products between them (a Neumann series of the whole chunk
cancels catastrophically where keys repeat).

The state is float32; the products take their operands in the inputs'
dtype (bfloat16 in a train step) and accumulate in float32, as the
attention kernels do. Plain XLA: no Pallas kernel here.

**The backward is the op's own** (``jax.custom_vjp``): the forward keeps
the operands and the state at every chunk's START (T / CHUNK states of
[H, dk, dv] float32) and nothing else. The backward walks the chunks in
reverse: a step makes the chunk's ``_intra`` again, turns the recurrence
round by hand (``_chunk_backward``: it is linear in the state), and takes
``_intra``'s own gradient by ``jax.vjp`` for that chunk alone, so what
autodiff keeps of the intra-chunk arithmetic is one chunk's, never the
sequence's. **Why a chunk a step and not the chunks' own arithmetic
batched ahead of a lean scan** (measured, PERF.md section 6, PR 39): the
batched form is bound by its intermediates' trips through HBM; one
chunk's (32 heads of [64, 128]) stay in fast memory, and forward +
backward take half the time.

``SCOPES`` are the named scopes this file opens around the parts of a KDA
layer's mixer that are neither projections nor the delta rule
(``ray_tpu/models/transformer.py`` opens ``attn_linear`` and the rest):
``kda_conv`` (the three convolutions, SiLU, the l2 norms) and
``kda_gate`` (the decay's and the output gate's low-rank maps, ``beta``,
softplus / exp, the head norm and the gate's product).
"""

from __future__ import annotations

import math
import jax
import jax.numpy as jnp

CHUNK = 64      # positions a chunk: one step of the sequential scan
SUB = 16        # positions a sub-block of a chunk's A and B
SCOPES = ("kda_conv", "kda_gate")
L2_EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST


def short_conv(x, w):
    """Causal depthwise convolution over positions: ``x`` [B, T, ...],
    ``w`` [K, ...] (the trailing dimensions are the channels) ->
    ``y_t = sum_j w[j] x_{t - K + 1 + j}``, zeros ahead of the row: a
    token's output reads itself and the K - 1 tokens before it. No bias.
    Sums in float32, returns ``x``'s dtype."""
    return _conv(x, w).astype(x.dtype)


def _conv(x, w):
    """``short_conv`` before its rounding: float32."""
    taps, length = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return sum(padded[:, j:j + length].astype(jnp.float32)
               * w[j].astype(jnp.float32) for j in range(taps))


def l2_norm(x):
    """``x / |x|`` over the last dimension, in float32."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True)
                               + L2_EPS)).astype(x.dtype)


def conv_silu(q, k, v, w_q, w_k, w_v):
    """A KDA layer's operands from its three projections [B, T, H, d]:
    ``q, k = l2_norm(silu(short_conv(.)))``, ``v = silu(short_conv(.))``
    (scope ``kda_conv``). Each chain runs in float32 and rounds ONCE, at
    its end: a chain of bfloat16 steps is rounded wherever XLA happens to
    cut its fusions, which the forward of a train step and a forward alone
    do differently."""
    with jax.named_scope("kda_conv"):
        return (l2_norm(jax.nn.silu(_conv(q, w_q))).astype(q.dtype),
                l2_norm(jax.nn.silu(_conv(k, w_k))).astype(k.dtype),
                jax.nn.silu(_conv(v, w_v)).astype(v.dtype))


def gates(h, w):
    """(``g`` [B, T, H, dk] float32, the log-decay per channel ``-exp(A_log)
    x softplus(W_f2 (W_f1 h) + dt_bias)``; ``beta`` [B, T, H] float32,
    ``sigmoid(W_b h)``) of the normed input ``h`` [B, T, D] (scope
    ``kda_gate``). ``w``: ``f_a`` [D, r], ``f_b`` [r, H, dk], ``dt_bias``
    [H, dk], ``A_log`` [H], ``w_beta`` [D, H]."""
    dt = h.dtype
    with jax.named_scope("kda_gate"):
        low = jnp.einsum("btd,dr->btr", h, w["f_a"].astype(dt))
        f = jnp.einsum("btr,rhk->bthk", low, w["f_b"].astype(dt),
                       preferred_element_type=jnp.float32)
        g = -jnp.exp(w["A_log"].astype(jnp.float32))[:, None] * \
            jax.nn.softplus(f + w["dt_bias"].astype(jnp.float32))
        beta = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bth", h, w["w_beta"].astype(dt),
            preferred_element_type=jnp.float32))
        return g, beta


def gated_head_norm(o, h, w, *, eps: float):
    """``rmsnorm_head(o) * sigmoid(W_g2 (W_g1 h))``: ``o`` [B, T, H, dv]
    the delta rule's output, normed over each head's dv values with ONE
    weight ``o_norm`` [dv] all heads share, times the output gate of the
    normed input ``h`` (scope ``kda_gate``). ``w``: ``g_a`` [D, r],
    ``g_b`` [r, H, dv], ``o_norm`` [dv]."""
    dt = h.dtype
    with jax.named_scope("kda_gate"):
        low = jnp.einsum("btd,dr->btr", h, w["g_a"].astype(dt))
        gate = jnp.einsum("btr,rhk->bthk", low, w["g_b"].astype(dt),
                          preferred_element_type=jnp.float32)
        of = o.astype(jnp.float32)
        normed = of * jax.lax.rsqrt(
            jnp.mean(of * of, -1, keepdims=True) + eps)
        return (normed * w["o_norm"].astype(jnp.float32)
                * jax.nn.sigmoid(gate)).astype(dt)


def log_decay_min(g):
    """The most negative cumulative log-decay inside any chunk: how near
    the chunked form runs to float32's range (no gradient)."""
    with jax.named_scope("kda_gate"):
        g = jax.lax.stop_gradient(g)
        pad = -g.shape[1] % CHUNK
        if pad:
            g = jnp.pad(g, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return g.reshape(g.shape[0], -1, CHUNK, *g.shape[2:]).sum(2).min()


# -- what a chunk needs of its own tokens -------------------------------------

def _unit_lower_inverse(m):
    """Inverse of ``I + strictly_lower(m)`` for ``m`` [..., SUB, SUB], by
    forward substitution a row at a time (SUB static steps)."""
    n = m.shape[-1]
    eye = jnp.eye(n, dtype=m.dtype)
    rows = [jnp.broadcast_to(eye[0], m.shape[:-2] + (n,))]
    for i in range(1, n):
        done = jnp.stack(rows, axis=-2)                     # [..., i, n]
        rows.append(eye[i] - jnp.einsum("...j,...jk->...k", m[..., i, :i],
                                        done, precision=_HIGHEST))
    return jnp.stack(rows, axis=-2)


def _block_lower_inverse(m):
    """Inverse of ``I + strictly_lower(m)`` for ``m`` [..., C, C]: the SUB
    x SUB diagonal blocks by forward substitution, then pairs of blocks
    merged, ``[[A, 0], [X, D]]^-1 = [[A^-1, 0], [-D^-1 X A^-1, D^-1]]``,
    until one block is the chunk."""
    size, c = SUB, m.shape[-1]
    lead = m.shape[:-2]
    blocks = m.reshape(*lead, c // size, size, c // size, size)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(c // size)], -3)
    inv = _unit_lower_inverse(diag)                        # [..., c/size, s, s]
    while size < c:
        pairs = c // (2 * size)
        view = m.reshape(*lead, pairs, 2, size, pairs, 2, size)
        below = jnp.stack([view[..., p, 1, :, p, 0, :]
                           for p in range(pairs)], -3)      # [..., pairs, s, s]
        inv = inv.reshape(*lead, pairs, 2, size, size)
        a, d = inv[..., 0, :, :], inv[..., 1, :, :]
        x = -jnp.einsum("...ij,...jk,...kl->...il", d, below, a,
                        precision=_HIGHEST)
        top = jnp.concatenate([a, jnp.zeros_like(a)], -1)
        inv = jnp.concatenate([top, jnp.concatenate([x, d], -1)], -2)
        size *= 2
    return inv.reshape(*lead, c, c)


def _intra(q, k, v, g, beta):
    """What a chunk needs of its own tokens. Operands head-major, [B, H,
    C, d] (``beta`` [B, H, C], ``g`` float32; any leading dimensions) ->
    (W [.., C, dk], U0 [.., C, dv], Qt [.., C, dk], Bm [.., C, C], Kbar
    [.., C, dk] in the operands' dtype; gamma [.., dk] float32): the
    module docstring's ``U = U0 - W S``, ``O = Qt S + Bm U``, ``S' = gamma
    * S + Kbar^T U``."""
    dt, f32 = q.dtype, jnp.float32
    lead, (c, dk) = q.shape[:-2], q.shape[-2:]
    n_sub = c // SUB
    scale = 1.0 / math.sqrt(dk)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    big = jnp.cumsum(g, axis=-2)                            # G, inclusive
    sub = lambda a: a.reshape(*lead, n_sub, SUB, a.shape[-1])
    big_s, k_s, q_s = sub(big), sub(kf), sub(qf)
    # below the diagonal blocks: rows against columns, both scaled by a
    # decay relative to R, the cumulative log-decay ahead of the row block
    ref = big_s[..., 0, :] - sub(g)[..., 0, :]              # [.., n_sub, dk]
    row = jnp.exp(big_s - ref[..., None, :])                # exponent <= 0
    ahead = (jnp.arange(c)[None, :]
             < (jnp.arange(n_sub) * SUB)[:, None])[..., None]   # [n_sub, C, 1]
    col = jnp.exp(jnp.where(
        ahead, ref[..., None, :] - big[..., None, :, :], -jnp.inf))
    k_col = (kf[..., None, :, :] * col).astype(dt)          # [.., n_sub, C, dk]
    below = lambda rows: jnp.einsum(
        "...irc,...isc->...irs", (rows * row).astype(dt), k_col,
        preferred_element_type=f32).reshape(*lead, c, c)
    # on the diagonal blocks: exp(G_t - G_s) itself, pair by pair
    low = jnp.tril(jnp.ones((SUB, SUB), bool))[..., None]
    decay = jnp.exp(jnp.where(
        low, big_s[..., :, None, :] - big_s[..., None, :, :], -jnp.inf))
    keyed = decay * k_s[..., None, :, :]                    # [.., t, s, dk]
    eye = jnp.eye(n_sub, dtype=f32)[:, None, :, None]
    on = lambda rows: ((rows[..., :, None, :] * keyed).sum(-1)
                       [..., :, :, None, :] * eye).reshape(*lead, c, c)
    a_mat = below(k_s) + jnp.tril(on(k_s), -1)
    b_mat = below(q_s) + on(q_s)
    inv = _block_lower_inverse(beta[..., :, None] * a_mat).astype(dt)
    gam = jnp.exp(big)
    w = jnp.einsum("...ts,...sc->...tc", inv,
                   (beta[..., None] * kf * gam).astype(dt),
                   preferred_element_type=f32)
    u0 = jnp.einsum("...ts,...se->...te", inv,
                    (beta[..., None] * vf).astype(dt),
                    preferred_element_type=f32)
    last = big[..., -1:, :]
    return (w.astype(dt), u0.astype(dt), (qf * gam * scale).astype(dt),
            (b_mat * scale).astype(dt), (kf * jnp.exp(last - big)).astype(dt),
            jnp.exp(last[..., 0, :]))


# -- the scan over chunks that carries the state -------------------------------

def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _chunk_forward(state, ops):
    """One chunk from the state it starts with ([B, H, dk, dv] float32)
    -> (the state after it, o [B, H, C, dv]): ``U = U0 - W S``, ``O = Qt S
    + Bm U``, ``S' = gamma * S + Kbar^T U``; ``gamma * S`` stays float32."""
    w, u0, qt, bm, kbar, gamma = ops
    dt = w.dtype
    sd = state.astype(dt)
    u = (u0.astype(jnp.float32) - _dot("bhtc,bhce->bhte", w, sd)).astype(dt)
    o = _dot("bhtc,bhce->bhte", qt, sd) + _dot("bhts,bhse->bhte", bm, u)
    after = gamma[..., None] * state + _dot("bhsc,bhse->bhce", kbar, u)
    return after, o.astype(dt)


def _chunk_backward(d_after, ops, state, d_o):
    """The reverse of ``_chunk_forward`` by hand (it is linear in the
    state): ``d_after`` the gradient of the state AFTER the chunk, ``d_o``
    [B, H, C, dv] -> (the gradient of the state the chunk started from,
    the gradients of ``ops``)."""
    w, u0, qt, bm, kbar, gamma = ops
    dt = w.dtype
    sd, dsd = state.astype(dt), d_after.astype(dt)
    u = (u0.astype(jnp.float32) - _dot("bhtc,bhce->bhte", w, sd)).astype(dt)
    du = (_dot("bhts,bhte->bhse", bm, d_o)
          + _dot("bhsc,bhce->bhse", kbar, dsd)).astype(dt)
    before = (_dot("bhtc,bhte->bhce", qt, d_o) + gamma[..., None] * d_after
              - _dot("bhtc,bhte->bhce", w, du))
    return before, (-_dot("bhte,bhce->bhtc", du, sd).astype(dt), du,
                    _dot("bhte,bhce->bhtc", d_o, sd).astype(dt),
                    _dot("bhte,bhse->bhts", d_o, u).astype(dt),
                    _dot("bhse,bhce->bhsc", u, dsd).astype(dt),
                    (state * d_after).sum(-1))


# -- the op -------------------------------------------------------------------

def _forward(operands, keep: bool):
    """``operands``: q, k, v, g, beta as [chunks, B, H, C, ...] -> o, or
    (o, the state at every chunk's start) with ``keep``."""
    q, v = operands[0], operands[2]
    state = jnp.zeros((*q.shape[1:3], q.shape[-1], v.shape[-1]), jnp.float32)

    def chunk(s, xs):
        after, o = _chunk_forward(s, _intra(*xs))
        return after, ((o, s) if keep else o)

    return jax.lax.scan(chunk, state, operands)[1]


@jax.custom_vjp
def _delta_rule(q, k, v, g, beta):
    return _forward((q, k, v, g, beta), keep=False)


def _delta_rule_fwd(q, k, v, g, beta):
    o, starts = _forward((q, k, v, g, beta), keep=True)
    return o, (q, k, v, g, beta, starts)


def _delta_rule_bwd(kept, d_o):
    *operands, starts = kept

    def chunk(d_after, xs):
        ops_in, s, do = xs
        ops, back = jax.vjp(_intra, *ops_in)
        before, d_ops = _chunk_backward(d_after, ops, s, do)
        return before, back(d_ops)

    return jax.lax.scan(chunk, jnp.zeros(starts.shape[1:], jnp.float32),
                        (tuple(operands), starts, d_o), reverse=True)[1]


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def gated_delta_rule(q, k, v, g, beta):
    """The gated delta rule in chunks (module docstring): ``q``, ``k`` [B,
    T, H, dk], ``v`` [B, T, H, dv], ``g`` [B, T, H, dk] float32 (the
    log-decay per channel, <= 0), ``beta`` [B, T, H] -> ``o`` [B, T, H, dv]
    in ``q``'s dtype, ``S_0 = 0``. A ``T`` that is no whole number of
    chunks is padded behind the row with tokens that write nothing
    (``beta`` = 0) and forget nothing (``g`` = 0): no real token sees
    them. Differentiable in all five operands."""
    b, t, h, _ = q.shape
    pad = -t % CHUNK

    def lay_out(a):
        """[B, T, H, *] -> [chunks, B, H, C, *]"""
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(b, (t + pad) // CHUNK, CHUNK, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    o = _delta_rule(lay_out(q), lay_out(k), lay_out(v),
                    lay_out(g.astype(jnp.float32)),
                    lay_out(beta.astype(jnp.float32)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)          # [B, N, C, H, dv]
    return o.reshape(b, t + pad, h, -1)[:, :t]
