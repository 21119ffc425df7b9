"""Linear attention that carries a STATE along the sequence: the causal
depthwise short convolution and the gated delta rule, as Kimi Delta
Attention runs it (KDA; Kimi Linear, arXiv:2510.26692: a log-decay per
CHANNEL, as many key heads as value heads) and as Gated DeltaNet does
(Qwen3-Next: ONE log-decay a value head and token, fewer key heads than
value heads, the output gate ``silu(z)`` of a full-rank projection:
``head_gates``, ``silu_gated_head_norm``, and the paragraph on the
head-wise form below), the first ops here whose work is a recurrence over
positions and not a sum over (query, key) pairs.

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

**ONE decay a head** (``g`` shaped as ``beta``, [B, T, H]: the rule's
rank test) is the case ``g_t[c] = g_t`` for every channel, and the chunk's
matrices lose their sum over channels: ``A[t, s] = (k_t . k_s) exp(G_t -
G_s)``, ``B`` likewise, so a chunk is ONE product of its rows against its
keys times ONE [C, C] decay matrix a head, every exponent <= 0 as it
stands: no reference decay a sub-block, no scaling of rows and columns,
nothing pair by pair (``_head_matrices`` and its gradient in the kernels;
the scan spreads ``g`` over the lanes and stays the form they are tested
against). **Fewer key heads than value heads** (q, k [B, T, H / r, dk]):
value head ``j`` reads key head ``j // r``; a kernel step's block of q and
k holds its value heads' key heads through the same index map, and the
key head's gradient is the sum over the value heads it serves.

The state is float32; the products take their operands in the inputs'
dtype (bfloat16 in a train step) and accumulate in float32, as the
attention kernels do.

**Two implementations of that one algorithm**, chosen by what the call
shows (``_on_one_tpu``: the platform, the heads' width, the operands'
sharding or the mesh in force; no option): on a TPU at 128-wide heads
with one device under the operands, two Pallas kernels (forward and
backward, further down: a grid over chunks whose steps keep a chunk's
matrices, its inverse and the state in VMEM; a third of the scan's time
forward and a quarter backward at [1, 16384, 32, 128] on a v5e, PERF.md
section 6, PR 40); anywhere else (the CPU tests' 16-wide heads, a mesh)
the scan in plain XLA that the next paragraph describes, which is also
what the kernels are tested against.

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

**Between the gate code and the rule the arrays are FLAT**: ``g`` [B, T,
H * dk] float32 into the rule, ``o`` out of it into the head norm, a head
a 128-lane slice and 8 TOKENS in a (8, 128) tile's sublanes, which is how
the kernels' blocks ``[1, CHUNK, heads * d]`` read and write them. By
heads, [B, T, H, d], XLA tiles 8 HEADS in the sublanes, and every
crossing of a 268-MB float32 array between the two tilings is a pass over
HBM of 0.82 ms at [1, 16384, 32, 128]: after PR 40 a train step of four
KDA layers made 44 of them (16 for ``g`` and its gradient, 28 for ``o``
into ``gated_head_norm``, its statistic's broadcast and its output gate),
none of them arithmetic. So ``gates`` and ``gated_head_norm`` make their
low-rank maps by ONE plain matmul against ``f_b`` / ``g_b`` viewed [r, H *
d] (the contraction ``btr,rhk->bthk`` is, whose result XLA writes by
heads), take per-head vectors as [H * d] views, and the plain head norm's
mean over a head's lanes is a product with ``_head_lanes``; ``gated_delta_rule``
takes ``g`` flat or by heads and tells them apart by rank. The parameter
leaves keep their shapes: the views are of a megabyte of weights
(PERF.md section 6, PR 43; ``tests/test_kda_layout.py`` holds the step
compiled for a described v5e to it).

**q, k and v are flat the whole way too** (PR 49): the mixer projects them
as plain matmuls against ``wq`` / ``wk`` / ``wv`` viewed [D, H * d], so XLA
writes them with 8 tokens in a tile's sublanes; ``conv_silu`` takes and
returns [B, T, H * d]; the rule reads them as they are. Each of the three
chains (short convolution, SiLU, for q and k the l2 norm over a head's 128
lanes) is ONE pass over HBM forward and one backward, two Pallas kernels
(``_conv_fwd_kernel``, ``_conv_bwd_kernel``) over (batch, lane blocks, token
tiles) that run where the rule's kernels do (``_on_one_tpu``: one rule for
the whole mixer); elsewhere ``_chain``, the same float32 arithmetic in
plain XLA on the flat arrays viewed by heads. Either way a chain is float32
from the projection's bfloat16 to its own end and rounds ONCE, there: the
kernel at its store of ``y`` (and of ``dx``; ``dw`` stays float32), the
plain chain at its last ``astype``. By heads the chains were some twenty XLA
fusions a layer with the backward laid out positions-in-lanes, and q, k, v
crossed between the two tilings on the way in (unnamed transposes) and on
the way out (copies into the kernels' tiling): 87 + 11 + 10 ms of a 720-ms
step (PERF.md section 6, PR 49).

**The head norm behind the rule is ONE pass a direction too** (PR 54):
``rmsnorm_head(o) * gate``, the gate ``sigmoid(low @ g_b)`` of KDA's
low-rank map (``gated_head_norm``) or ``silu(z)`` of Gated DeltaNet's
projection (``silu_gated_head_norm``), one ``_head_norm`` handed the gate's
source. Two implementations, chosen as the chains' are (``_on_one_tpu``):
two Pallas kernels over the chains' tiles (``_norm_fwd_kernel``,
``_norm_bwd_kernel``: a head's mean of squares a float32 lane sum, ``low @
g_b`` made on the MXU a block at a time; the backward keeps the operands,
makes the statistic and the gate again and sums ``d_o_norm`` in its output
block), each at the rate a plain copy of its operands reaches on a v5e;
elsewhere ``_head_norm_plain``, which is what they are tested against.
**Arrays that no longer exist on the kernels' path**, each [B, T, H * dv]:
the output gate's float32 pre-activation (268 MB at [1, 16384, 4096],
written to be read once), the float32 spread of a head's statistic over
its lanes (a ``_head_lanes`` product at ``highest``), their gradients, and
the float32 gradient of the pre-activation, which XLA rounded in a pass
of its own under no scope: it leaves the backward kernel in the compute
dtype, ``dz`` as it is, or what ``d_low`` and ``d_g_b`` (two small XLA
matmuls) read. A plain layer made four passes forward where this makes
one: 1.64 -> 0.49 ms forward and 6.18 -> 2.31 forward + backward for KDA,
1.15 -> 0.64 and 3.49 -> 1.68 for Gated DeltaNet (PERF.md section 6, PR
54).

``SCOPES`` are the named scopes this file opens around the parts of a
linear (KDA or Gated DeltaNet) layer's mixer that are neither projections
nor the delta rule
(``ray_tpu/models/transformer.py`` opens ``attn_linear``, the mixers of
``ray_tpu/models/mixers.py`` the rest):
``kda_conv`` (the three convolutions, SiLU, the l2 norms: on a TPU the
chains' Pallas calls, which ``step_kda_kernel_ms`` and
``step_attn_kernel_ms`` therefore count beside the rule's) and
``kda_gate`` (the decay's and the output gate's low-rank maps, or Gated
DeltaNet's one decay a head; ``beta``, softplus / exp, the head norm and
the gate's product, ``sigmoid`` or ``silu(z)``: on a TPU the head norm's
Pallas calls, counted by the same two readers).
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp

CHUNK = 64      # positions a chunk: one step of the sequential scan
SUB = 16        # positions a sub-block of a chunk's A and B
SCOPES = ("kda_conv", "kda_gate")
L2_EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST
logger = logging.getLogger(__name__)


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
    """A KDA layer's operands from its three projections, FLAT on both
    sides: ``q``, ``k`` [B, T, H * dk], ``v`` [B, T, H * dv] (a head a
    slice of its ``d`` lanes), the taps ``w_*`` [K, H, d] as the leaves are
    -> ``q, k = l2_norm(silu(short_conv(.)))``, ``v = silu(short_conv(.))``
    in the same shapes (scope ``kda_conv``). Each chain runs in float32
    and rounds ONCE, at its end: a chain of bfloat16 steps is rounded
    wherever XLA happens to cut its fusions, which the forward of a train
    step and a forward alone do differently. Where ``_on_one_tpu`` finds
    the delta rule's kernels' case (one rule for the whole mixer), a chain
    is ONE Pallas pass forward and one backward over the flat arrays
    (``_chain_kernels``, further down); anywhere else ``_chain``, the same
    arithmetic in plain XLA on the flat arrays viewed by heads, which is
    also what the kernels are tested against."""
    with jax.named_scope("kda_conv"):
        fused = (_on_one_tpu(q, w_q.shape[-1], w_v.shape[-1])
                 and w_q.shape[0] - 1 <= _CONV_HALO)
        chain = _chain_kernels if fused else _chain
        return (chain(q, w_q, True), chain(k, w_k, True),
                chain(v, w_v, False))


def flat_conv_silu(x, w, bias=None):
    """A state-space layer's ONE chain on its flat ``[x | B | C]``
    projection ``x`` [B, T, C]: ``silu(short_conv(x) + bias)``, taps ``w``
    [K, C], ``bias`` [C] or None, no l2 norm (scope ``kda_conv``). On one
    TPU chip (``_one_tpu``) at whole 128-lane tiles and taps the halo
    holds, ONE Pallas pass forward and one backward (``_chain_kernels``,
    the bias a row beside the taps); anywhere else ``_chain``, which is
    also what the kernels are tested against."""
    with jax.named_scope("kda_conv"):
        fused = (x.shape[2] % _KERNEL_WIDTH == 0
                 and w.shape[0] - 1 <= _CONV_HALO and _one_tpu(x))
        return (_chain_kernels if fused else _chain)(x, w, False, bias)


def _chain(x, w, norm: bool, bias=None):
    """One chain in plain XLA: ``x`` [B, T, H * d], ``w`` [K, H, d] ->
    ``silu(short_conv(x))``, l2-normed over each head's ``d`` lanes with
    ``norm``; float32 throughout, rounded once to ``x``'s dtype. With a
    ``bias`` [H * d], ``silu(short_conv(x) + bias)`` (``flat_conv_silu``;
    ``w`` may be [K, C])."""
    y = _conv(x, w.reshape(w.shape[0], -1))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    y = jax.nn.silu(y)
    if norm:
        y = l2_norm(y.reshape(*x.shape[:2], *w.shape[1:])).reshape(x.shape)
    return y.astype(x.dtype)


def gates(h, w):
    """(``g`` [B, T, H * dk] float32, the log-decay per channel ``-exp(A_log)
    x softplus(W_f2 (W_f1 h) + dt_bias)``, FLAT as the delta rule's kernels
    read it (module docstring); ``beta`` [B, T, H] float32, ``sigmoid(W_b
    h)``) of the normed input ``h`` [B, T, D] (scope ``kda_gate``). ``w``:
    ``f_a`` [D, r], ``f_b`` [r, H, dk], ``dt_bias`` [H, dk], ``A_log`` [H],
    ``w_beta`` [D, H]: the leaves keep their shapes, the flat views are
    taken here. ``f`` is ONE plain matmul ``[B T, r] x [r, H * dk]``,
    bfloat16 operands summed in float32, whose result XLA writes with 8
    tokens in a tile's sublanes; ``g`` goes to ``gated_delta_rule`` with no
    reshape, transpose or copy, and its gradient comes back from the
    kernels flat and reaches softplus', the bias', ``A_log``'s and both
    matmuls' gradients flat."""
    dt = h.dtype
    rank, _, dk = w["f_b"].shape
    with jax.named_scope("kda_gate"):
        low = jnp.einsum("btd,dr->btr", h, w["f_a"].astype(dt))
        f = jnp.einsum("btr,rc->btc", low,
                       w["f_b"].astype(dt).reshape(rank, -1),
                       preferred_element_type=jnp.float32)
        rate = jnp.repeat(-jnp.exp(w["A_log"].astype(jnp.float32)), dk)
        g = rate * jax.nn.softplus(
            f + w["dt_bias"].astype(jnp.float32).reshape(-1))
        beta = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bth", h, w["w_beta"].astype(dt),
            preferred_element_type=jnp.float32))
        return g, beta


def head_gates(h, w):
    """Gated DeltaNet's two gates of the normed input ``h`` [B, T, D], ONE
    number a value head and token each, float32 [B, T, H] (scope
    ``kda_gate``): ``g = -exp(A_log) x softplus(W_a h + dt_bias)``, the
    log-decay a HEAD (<= 0; ``gated_delta_rule`` tells it from a decay per
    channel by its shape), and ``beta = sigmoid(W_b h)``. ``w``: ``w_a``,
    ``w_beta`` [D, H], ``dt_bias``, ``A_log`` [H]."""
    dt = h.dtype
    with jax.named_scope("kda_gate"):
        a, b = (jnp.einsum("btd,dh->bth", h, w[name].astype(dt),
                           preferred_element_type=jnp.float32)
                for name in ("w_a", "w_beta"))
        g = -jnp.exp(w["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a + w["dt_bias"].astype(jnp.float32))
        return g, jax.nn.sigmoid(b)


def _head_lanes(heads: int, d: int):
    """[H, H * d] float32: row ``h`` is 1 over head ``h``'s ``d`` lanes of
    a flat ``[.., H * d]`` array and 0 elsewhere. A flat array times its
    transpose is the sum over each head's lanes, ``[.., H]`` times it puts
    a head's number on each of its lanes: both as plain products of flat
    arrays, at ``highest`` (six bfloat16 passes; 0 and 1 are exact, so the
    sums are float32's), where a reduction or a broadcast over a [.., H,
    d] VIEW makes XLA change the flat array's tiling (module docstring)."""
    return jnp.repeat(jnp.eye(heads, dtype=jnp.float32), d, axis=1)


def gated_head_norm(o, h, w, *, eps: float):
    """``rmsnorm_head(o) * sigmoid(W_g2 (W_g1 h))``: ``o`` [B, T, H, dv]
    the delta rule's output, normed over each head's dv values with ONE
    weight ``o_norm`` [dv] all heads share, times the output gate of the
    normed input ``h`` (scope ``kda_gate``). ``w``: ``g_a`` [D, r],
    ``g_b`` [r, H, dv], ``o_norm`` [dv]: the leaves keep their shapes.
    Worked FLAT, [B, T, H * dv], as the delta rule's kernels write ``o``
    (module docstring), all float32, rounded once at the end. ``low = h @
    g_a`` [B, T, r] is a plain matmul either way; the norm and the gate
    are ``_head_norm``'s, which is handed ``(low, g_b)`` for the gate's
    source: on one TPU chip ONE Pallas pass forward and one backward that
    make ``low @ g_b`` on the MXU and never write the pre-activation;
    anywhere else ONE plain matmul ``[B T, r] x [r, H * dv]`` (``gates``'
    form), products with ``_head_lanes`` and an elementwise rest."""
    dt = h.dtype
    with jax.named_scope("kda_gate"):
        low = jnp.einsum("btd,dr->btr", h, w["g_a"].astype(dt))
        g_b = w["g_b"].astype(dt).reshape(low.shape[-1], -1)
        return _head_norm(o, (low, g_b), w["o_norm"], eps)


def silu_gated_head_norm(o, z, weight, *, eps: float):
    """Gated DeltaNet's output: ``rmsnorm_head(o; weight) * silu(z)``, ``o``
    [B, T, H, dv] the delta rule's output, ``z`` [B, T, H * dv] the FLAT
    full-rank gate projection of the block's normed input (where KDA's
    gate is ``sigmoid`` of a low-rank map, ``gated_head_norm``), ``weight``
    [dv] shared by the heads (scope ``kda_gate``). Worked flat and in
    float32, rounded once at the end, as ``gated_head_norm`` is, by the
    same ``_head_norm`` handed ``(z,)``: the same one Pallas pass a
    direction on one TPU chip (``z`` read as it is, ``dz`` written
    rounded), the same plain form anywhere else."""
    with jax.named_scope("kda_gate"):
        return _head_norm(o, (z,), weight, eps)


def _head_norm(o, source, weight, eps: float):
    """``rmsnorm_head(o; weight)`` times the output gate -> ``o``'s shape
    [B, T, H, dv] and dtype. **The gate is what ``source`` holds**: ``(z,)``
    [B, T, H * dv] for ``silu(z)``, ``(low, g_b)`` [B, T, r], [r, H * dv]
    for ``sigmoid(low @ g_b)``. **Two implementations, chosen as the
    chains' are** (``_on_one_tpu``: one rule for the whole mixer, no
    option): the Pallas kernels further down (``_head_norm_kernels``: one
    pass over ``o`` and the source forward, one backward; no float32 [B,
    T, H * dv] array reaches HBM) or ``_head_norm_plain``, the same
    arithmetic in plain XLA, which is also what the kernels are tested
    against."""
    fused = _on_one_tpu(o, o.shape[-1], o.shape[-1])
    return (_head_norm_kernels if fused else _head_norm_plain)(
        o, source, weight, eps)


def _head_norm_plain(o, source, weight, eps: float):
    """``_head_norm`` in plain XLA on the flat arrays: the pre-activation
    of a low-rank gate ONE matmul ``[B T, r] x [r, H * dv]`` summed in
    float32, the head's statistic by ``_normed_heads``, the rest
    elementwise; float32 throughout, rounded once to ``o``'s dtype."""
    if len(source) == 1:
        gate = jax.nn.silu(source[0].astype(jnp.float32))
    else:
        gate = jax.nn.sigmoid(jnp.einsum(
            "btr,rc->btc", *source, preferred_element_type=jnp.float32))
    return (_normed_heads(o, weight, eps) * gate).astype(o.dtype).reshape(
        o.shape)


def _normed_heads(o, weight, eps: float):
    """``rmsnorm_head(o)`` of ``o`` [B, T, H, dv] with the ONE weight [dv]
    all heads share -> FLAT float32 [B, T, H * dv]: the mean over a head's
    lanes and its spread back are products with ``_head_lanes``."""
    b, t, heads, dv = o.shape
    of = o.astype(jnp.float32).reshape(b, t, heads * dv)
    lanes = _head_lanes(heads, dv)
    mean = jnp.einsum("btc,hc->bth", of * of, lanes,
                      precision=_HIGHEST) / dv
    scale = jnp.einsum("bth,hc->btc", jax.lax.rsqrt(mean + eps), lanes,
                       precision=_HIGHEST)
    return of * scale * jnp.tile(weight.astype(jnp.float32), heads)


def log_decay_min(g, chunk: int = CHUNK):
    """The most negative cumulative log-decay inside any chunk of
    ``chunk`` positions: how near the chunked form runs to float32's
    range (no gradient). ``g`` [B, T, H * dk], [B, T, H, dk] or [B, T, H]:
    the chunks' sums are over positions alone."""
    with jax.named_scope("kda_gate"):
        g = jax.lax.stop_gradient(g)
        pad = -g.shape[1] % chunk
        if pad:
            g = jnp.pad(g, ((0, 0), (0, pad)) + ((0, 0),) * (g.ndim - 2))
        return g.reshape(g.shape[0], -1, chunk, *g.shape[2:]).sum(2).min()


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


# -- the same chunk, as Pallas kernels ------------------------------------------
#
# One grid step is one chunk of ``_KERNEL_HEADS`` heads. What ``_intra`` and
# ``_chunk_forward`` (backward: ``_chunk_backward`` and ``_intra``'s own
# gradient, by hand) make of it lives in VMEM and nowhere else; the state,
# float32 and TRANSPOSED ([dv, dk]: ``gamma`` then scales its lanes and
# every product with it is one the MXU takes as it stands), is a scratch
# that the sequential grid dimension over chunks carries. The operands
# arrive as [B, T, H * d]: a head is a 128-lane slice of a chunk's block,
# and nothing is transposed on the way in or out. ``beta`` and its
# gradient are rows, [B, H / heads, T / CHUNK, heads, CHUNK] (``_column``
# and ``_row`` turn them inside the kernel: [.., T, heads] would be 64
# times its size in HBM's tiles).
#
# A head's chunk is ONE chain of some thirty small products, each waiting
# for the last, and the MXU answers in a hundred cycles: so every stage
# runs for all the heads of the step before the next stage starts (the
# loops over ``xs``), which puts independent products side by side in the
# program for the scheduler to overlap.

_KERNEL_HEADS = 4
_F32 = jnp.float32
_NT, _TN = ((1,), (1,)), ((0,), (0,))


def _mm(a, b, dims=((1,), (0,)), precision=None):
    """a @ b (``_NT``: a @ b^T, ``_TN``: a^T @ b), summed in float32."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _split(a):
    """float32 -> (its bfloat16 rounding, the rest's bfloat16 rounding)."""
    high = a.astype(jnp.bfloat16)
    return high, (a - high.astype(_F32)).astype(jnp.bfloat16)


def _running_sum(x, reverse: bool = False):
    """Inclusive running sums of ``x`` [C, d] float32 down its rows (up
    them with ``reverse``), exact to float32: a triangle of ones against
    ``x`` split into three bfloat16 parts that sum to it, so every product
    is exact whatever precision the MXU gives float32 operands."""
    c = x.shape[0]
    r, s = _iota((c, c), 0), _iota((c, c), 1)
    ones = jnp.where((r <= s) if reverse else (r >= s), 1.0, 0.0).astype(
        jnp.bfloat16)
    high = x.astype(jnp.bfloat16)
    middle, low = _split(x - high.astype(_F32))
    return _mm(ones, high) + _mm(ones, middle) + _mm(ones, low)


def _under(whole, top: int, rows):
    """``whole`` with its rows from ``top`` down replaced by ``rows``."""
    return jnp.concatenate([whole[:top], rows], 0) if top else rows


def _column(row):
    """[1, C] -> [C, 1]."""
    c = row.shape[1]
    return jnp.sum(jnp.where(_iota((c, c), 0) == _iota((c, c), 1), row, 0.0),
                   1, keepdims=True)


def _row(column):
    """[C, 1] -> [1, C]."""
    c = column.shape[0]
    return jnp.sum(jnp.where(_iota((c, c), 0) == _iota((c, c), 1), column,
                             0.0), 0, keepdims=True)


class _Chunk:
    """What one head's chunk is made into, by name (``_kernel_intra``)."""


def _kernel_intra(operands):
    """``_intra`` inside a kernel. ``operands``: a head each, (``q``, ``k``
    [C, dk], ``v`` [C, dv] in the operands' dtype, ``g`` [C, dk] and
    ``beta`` [C, 1] float32) -> a ``_Chunk`` each with ``_intra``'s six
    results (``w``, ``u0``, ``qt``, ``bm``, ``kbar``; ``gamma`` [1, dk])
    and what the backward reads besides. ``g`` as a ROW, [1, C], is ONE
    log-decay a head and token (Gated DeltaNet): ``A`` and ``B`` are then
    ``_head_matrices``', and ``gamma`` is [1, 1]. Else sub-block by sub-block as
    ``_intra``: below the diagonal one product against a reference decay,
    on it pair by pair, a column ``s`` of the block a step; the SUB x SUB
    diagonal blocks of the inverse by elimination in float32, all of them
    side by side in the lanes of one [SUB, C] array; then
    ``_block_lower_inverse``'s merges, float32 products at ``highest``
    whatever the chunk's dtype, as there (``inv`` is the float32 inverse
    the backward's gradient goes through, ``inv_d`` its rounding)."""
    xs = []
    for q, k, v, g, beta in operands:
        x = _Chunk()
        x.dt, x.g, x.beta = q.dtype, g, beta
        x.qd, x.kd = q, k
        x.qf, x.kf, x.vf = [a.astype(_F32) for a in (q, k, v)]
        x.scale = 1.0 / math.sqrt(q.shape[1])
        xs.append(x)
    matrices = _head_matrices if _by_head(xs) else _channel_matrices
    return _kernel_inverse(xs, *matrices(xs))


def _by_head(xs) -> bool:
    """Whether the chunks' ``g`` is a row: ONE decay a head and token."""
    return xs[0].g.shape[0] == 1


def _head_matrices(xs):
    """``A`` and ``B`` of a chunk whose heads have ONE log-decay a token
    (``x.g`` a row [1, C]): ``A[t, s] = (k_t . k_s) exp(G_t - G_s)``, so
    one product of the chunk's rows against its keys and ONE [C, C] decay
    matrix a head, every exponent <= 0 as it stands: no reference decay a
    sub-block, no scaling of rows and columns by channel, nothing pair by
    pair. Leaves what ``_channel_matrices`` leaves (``big`` here [C, 1],
    which every later use spreads over the lanes)."""
    c = xs[0].qf.shape[0]
    n_sub = c // SUB
    r, s = _iota((c, c), 0), _iota((c, c), 1)
    lane, sub = _iota((SUB, c), 1), _iota((SUB, c), 0)
    for x in xs:
        # the running sums down the chunk, as a column and as a row: sums
        # of float32 on the VPU, exact
        x.big = jnp.sum(jnp.where(r >= s, x.g, 0.0), 1, keepdims=True)
        x.big_row = jnp.sum(jnp.where(r <= s, _column(x.g), 0.0), 0,
                            keepdims=True)
        x.decay = jnp.exp(jnp.minimum(x.big - x.big_row, 0.0))
    for x in xs:
        both = _mm(jnp.concatenate([x.kd, x.qd], 0), x.kd, _NT)  # [2 C, C]
        x.a = jnp.where(r > s, both[:c] * x.decay, 0.0)
        x.b = jnp.where(r >= s, both[c:] * x.decay, 0.0)
        x.m = x.beta * x.a
    for x in xs:
        # column j of every diagonal block of beta * A, across its lanes
        x.columns = []
        for j in range(SUB - 1):
            col = jnp.zeros((SUB, c), _F32)
            for i in range(n_sub):
                r0 = i * SUB
                col = jnp.where(lane // SUB == i,
                                x.m[r0:r0 + SUB, r0 + j:r0 + j + 1], col)
            x.columns.append(col)
        x.packed = jnp.where(lane % SUB == sub, 1.0, 0.0)
    return lane, sub


def _channel_matrices(xs):
    """``A`` and ``B`` of a chunk whose log-decay is per CHANNEL (``x.g``
    [C, dk]), sub-block by sub-block as ``_intra`` (``_kernel_intra``)."""
    c, dk = xs[0].qf.shape
    n_sub = c // SUB
    for x in xs:
        x.big = _running_sum(x.g)
    lane, sub = _iota((SUB, c), 1), _iota((SUB, c), 0)
    # the lanes of the rows from ``top`` down (a slice of ``lane`` is
    # something Mosaic's compiler fails on)
    lanes_under = {top: _iota((SUB - top, c), 1) for top in range(0, SUB, 8)}
    for x in xs:
        x.a_rows, x.b_rows, x.rows, x.cols = [], [], [], []
        x.columns = [jnp.zeros((SUB, c), _F32) for _ in range(SUB - 1)]
        x.beta_lanes = jnp.zeros((SUB, c), _F32)
    for i in range(n_sub):
        r0 = i * SUB
        for x in xs:
            big_i, k_i, q_i = (a[r0:r0 + SUB] for a in (x.big, x.kf, x.qf))
            acc_a = acc_b = jnp.zeros((SUB, c), _F32)
            for s in range(SUB):
                # column s is read from its own row down: the 8-row tiles
                # above that row are left out
                top = s // 8 * 8
                lane_s = lanes_under[top]
                at = lane_s == r0 + s
                keyed = jnp.exp(jnp.minimum(big_i[top:] - big_i[s:s + 1],
                                            0.0)) * k_i[s:s + 1]
                col_a = jnp.sum(keyed * k_i[top:], -1, keepdims=True)
                col_b = jnp.sum(keyed * q_i[top:], -1, keepdims=True)
                acc_a = _under(acc_a, top, jnp.where(at, col_a, acc_a[top:]))
                acc_b = _under(acc_b, top, jnp.where(at, col_b, acc_b[top:]))
                if s < SUB - 1:
                    x.columns[s] = _under(x.columns[s], top, jnp.where(
                        lane_s // SUB == i, col_a, x.columns[s][top:]))
            x.beta_lanes = jnp.where(lane // SUB == i, x.beta[r0:r0 + SUB],
                                     x.beta_lanes)
            x.a_rows.append(jnp.where(sub + r0 > lane, acc_a, 0.0))
            x.b_rows.append(jnp.where(sub + r0 >= lane, acc_b, 0.0))
            ref = big_i[:1] - x.g[r0:r0 + 1]
            x.rows.append(jnp.exp(big_i - ref))             # exponent <= 0
            if i:
                x.cols.append(jnp.exp(ref - x.big[:r0]))    # exponent <= 0
        for x in xs:
            if not i:
                continue
            k_col = jnp.concatenate(
                [(x.kf[:r0] * x.cols[-1]).astype(x.dt),
                 jnp.zeros((c - r0, dk), x.dt)], 0)
            rows = jnp.concatenate(
                [x.kf[r0:r0 + SUB] * x.rows[i], x.qf[r0:r0 + SUB] * x.rows[i]],
                0).astype(x.dt)
            below = _mm(rows, k_col, _NT)                   # [2 SUB, C]
            x.a_rows[i] = x.a_rows[i] + below[:SUB]
            x.b_rows[i] = x.b_rows[i] + below[SUB:]
    for x in xs:
        x.a = jnp.concatenate(x.a_rows, 0)
        x.m = x.beta * x.a
        x.b = jnp.concatenate(x.b_rows, 0)
        # column j of every diagonal block of beta * A, across its lanes
        x.columns = [jnp.where(sub > j, col * x.beta_lanes, 0.0)
                     for j, col in enumerate(x.columns)]
        x.packed = jnp.where(lane % SUB == sub, 1.0, 0.0)
    return lane, sub


def _kernel_inverse(xs, lane, sub):
    """``_kernel_intra`` from ``A``, ``B`` and the diagonal blocks' columns
    on: the inverse and the chunk's six results."""
    c, dk = xs[0].qf.shape
    n_sub = c // SUB
    # the diagonal blocks' inverses: X <- X - n_j (x) X[j], j ascending,
    # n_j column j of the block (rows > j)
    for j in range(SUB - 1):
        for x in xs:
            x.packed = x.packed - x.columns[j] * x.packed[j:j + 1]
    for x in xs:
        x.inv = jnp.concatenate([jnp.where(lane // SUB == i, x.packed, 0.0)
                                 for i in range(n_sub)], 0)     # [C, C]
    r, s = _iota((c, c), 0), _iota((c, c), 1)
    size = SUB
    while size < c:
        under = ((r // size) % 2 == 1) & (s // size == r // size - 1)
        for x in xs:
            x.part = _mm(x.inv, jnp.where(under, x.m, 0.0),
                         precision=_HIGHEST)
        for x in xs:
            x.inv = x.inv - _mm(x.part, x.inv, precision=_HIGHEST)
        size *= 2
    for x in xs:
        x.inv_d = x.inv.astype(x.dt)
        x.gam = jnp.exp(x.big)
        x.kg_vb = jnp.concatenate([(x.beta * x.kf * x.gam).astype(x.dt),
                                   (x.beta * x.vf).astype(x.dt)], 1)
    for x in xs:
        w_u0 = _mm(x.inv_d, x.kg_vb).astype(x.dt)           # [C, dk + dv]
        x.w, x.u0 = w_u0[:, :dk], w_u0[:, dk:]
        last = x.big[c - 1:]
        x.tail = jnp.exp(last - x.big)
        x.qt = (x.qf * x.gam * x.scale).astype(x.dt)
        x.bm = (x.b * x.scale).astype(x.dt)
        x.kbar = (x.kf * x.tail).astype(x.dt)
        x.gamma = jnp.exp(last)
    return xs


def _head_slices(h: int, dk: int, dv: int, rep: int = 1):
    """(the lanes of value head ``h``'s KEY head, ``h // rep`` (``rep``
    value heads read one key head), its own lanes of v and o) of a step's
    blocks."""
    return (slice(h // rep * dk, (h // rep + 1) * dk),
            slice(h * dv, (h + 1) * dv))


def _load_heads(heads, dk, dv, rep, q_ref, k_ref, v_ref, g_ref, beta_ref):
    """A step's operands, a value head each. ``g_ref`` blocked like
    ``beta_ref`` (rows) is ONE decay a head: handed on as a row."""
    out = []
    for h in range(heads):
        keys, values = _head_slices(h, dk, dv, rep)
        out.append((q_ref[0, :, keys], k_ref[0, :, keys], v_ref[0, :, values],
                    g_ref[0, 0, 0, h:h + 1] if len(g_ref.shape) == 5
                    else g_ref[0, :, keys],
                    _column(beta_ref[0, 0, 0, h:h + 1])))
    return out


def _delta_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                      heads: int, dk: int, dv: int, rep: int):
    import jax.experimental.pallas as pl

    *starts_ref, state = rest       # the chunk-start states only if kept

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    xs = _kernel_intra(_load_heads(heads, dk, dv, rep, q_ref, k_ref, v_ref,
                                   g_ref, beta_ref))
    for h, x in enumerate(xs):
        x.s = state[h]
        if starts_ref:
            starts_ref[0][0, 0, h] = x.s
        # W and Qt against the state in one product
        x.by_state = _mm(jnp.concatenate([x.w, x.qt], 0), x.s.astype(x.dt),
                         _NT)
    c = xs[0].qf.shape[0]
    for x in xs:
        x.u = (x.u0.astype(_F32) - x.by_state[:c]).astype(x.dt)
    for h, x in enumerate(xs):
        o_ref[0, :, _head_slices(h, dk, dv)[1]] = (
            x.by_state[c:] + _mm(x.bm, x.u)).astype(o_ref.dtype)
        state[h] = x.gamma * x.s + _mm(x.u, x.kbar, _TN)


def _delta_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref,
                      do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                      d_state, *, heads: int, dk: int, dv: int, rep: int):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        d_state[...] = jnp.zeros_like(d_state)

    xs = _kernel_intra(_load_heads(heads, dk, dv, rep, q_ref, k_ref, v_ref,
                                   g_ref, beta_ref))
    c = xs[0].qf.shape[0]
    by_head = _by_head(xs)
    r, col = _iota((c, c), 0), _iota((c, c), 1)
    # ``_chunk_backward``, the state and its gradient transposed
    for h, x in enumerate(xs):
        x.s, x.d_after = starts_ref[0, 0, h], d_state[h]
        x.d_o = do_ref[0, :, _head_slices(h, dk, dv)[1]]
        x.sd, x.dsd = x.s.astype(x.dt), x.d_after.astype(x.dt)
    for x in xs:
        x.u = (x.u0.astype(_F32) - _mm(x.w, x.sd, _NT)).astype(x.dt)
        x.du = (_mm(x.bm, x.d_o, _TN) + _mm(x.kbar, x.dsd, _NT)).astype(x.dt)
    for h, x in enumerate(xs):
        d_state[h] = (_mm(x.d_o, x.qt, _TN) + x.gamma * x.d_after
                      - _mm(x.du, x.w, _TN))
        by_state = _mm(jnp.concatenate([x.du, x.d_o], 0), x.sd)
        x.d_w = (-by_state[:c]).astype(x.dt)
        x.d_qt = by_state[c:]
        x.d_bm = _mm(x.d_o, x.u, _NT)
        x.d_kbar = _mm(x.u, x.dsd)
        x.d_gamma = jnp.sum(x.s * x.d_after, 0, keepdims=True)
    # ``_intra``'s gradient. The inverse T of I + tril(beta * A, -1): its
    # gradient dT = [dW | dU] [Kg | Vb]^T arrives rounded to the chunk's
    # dtype (T entered both products so), and beta * A's is -T^T dT T^T
    # under the diagonal, through the float32 inverse at ``highest`` as
    # ``_block_lower_inverse``'s own gradient takes it
    for x in xs:
        d_w_u = jnp.concatenate([x.d_w, x.du], 1)
        x.d_kg_vb = _mm(x.inv_d, d_w_u, _TN)
        x.d_inv = _mm(d_w_u, x.kg_vb, _NT).astype(x.dt).astype(_F32)
    for x in xs:
        x.d_inv = _mm(x.inv, x.d_inv, _TN, precision=_HIGHEST)
    for x in xs:
        d_m = jnp.where(r > col, -_mm(x.d_inv, x.inv, _NT,
                                      precision=_HIGHEST), 0.0)
        d_kg, d_vb = x.d_kg_vb[:, :dk], x.d_kg_vb[:, dk:]
        x.d_a = x.beta * d_m
        x.d_b = jnp.where(r >= col, x.d_bm * x.scale, 0.0)
        x.d_beta = (jnp.sum(d_m * x.a, -1, keepdims=True)
                    + jnp.sum(d_kg * x.kf * x.gam, -1, keepdims=True)
                    + jnp.sum(d_vb * x.vf, -1, keepdims=True))
        x.d_v = d_vb * x.beta
        kept = x.d_kbar * x.tail                # d_kbar as k receives it
        written = d_kg * x.beta * x.gam         # d_kg likewise
        x.d_k = written + kept
        x.d_q = x.d_qt * x.gam * x.scale
        x.d_big = x.kf * (written - kept) + x.qf * x.d_q
        x.d_last = (jnp.sum(kept * x.kf, 0, keepdims=True)
                    + x.d_gamma * x.gamma)
        if by_head:         # ONE number a token: the channels' sum
            x.d_big = jnp.sum(x.d_big, 1, keepdims=True)
            x.d_last = jnp.sum(x.d_last, 1, keepdims=True)
    (_head_matrices_grad if by_head else _channel_matrices_grad)(xs)
    at_last = _iota((c, 1), 0) == c - 1
    for h, x in enumerate(xs):
        keys, values = _head_slices(h, dk, dv, rep)
        d_big = x.d_big + jnp.where(at_last, x.d_last, 0.0)
        d_q, d_k = x.d_q + x.q_rows, x.d_k + x.k_rows + x.k_cols
        # a key head's gradient is the sum over the value heads it serves
        if h % rep:
            d_q, d_k = d_q + of_key[0], d_k + of_key[1]
        of_key = d_q, d_k
        if h % rep == rep - 1:
            dq_ref[0, :, keys] = d_q.astype(dq_ref.dtype)
            dk_ref[0, :, keys] = d_k.astype(dk_ref.dtype)
        dv_ref[0, :, values] = x.d_v.astype(dv_ref.dtype)
        if by_head:         # the running sum up the chunk, as a row
            dg_ref[0, 0, 0, h:h + 1] = jnp.sum(
                jnp.where(r >= col, d_big, 0.0), 0, keepdims=True)
        else:
            dg_ref[0, :, keys] = _running_sum(d_big, reverse=True)
        dbeta_ref[0, 0, 0, h:h + 1] = _row(x.d_beta)


def _head_matrices_grad(xs):
    """The gradient of ``_head_matrices``' ``A = (K K^T) * D`` and ``B = (Q
    K^T) * D`` from ``x.d_a`` and ``x.d_b`` (masked as ``A`` and ``B``
    are): k as a row of ``A`` and q as a row of ``B`` (``k_rows``,
    ``q_rows``: one product), k as a column of both (``k_cols``: one
    more), and the decay's, ``d_a A + d_b B`` summed along a token's row
    less the same summed down its column, added to ``x.d_big`` [C, 1]."""
    c = xs[0].qf.shape[0]
    for x in xs:
        both = jnp.concatenate([x.d_a * x.decay, x.d_b * x.decay], 0).astype(
            x.dt)                                            # [2 C, C]
        rows = _mm(both, x.kd)
        x.k_rows, x.q_rows = rows[:c], rows[c:]
        x.k_cols = _mm(both, jnp.concatenate([x.kd, x.qd], 0), _TN)
        through = x.d_a * x.a + x.d_b * x.b
        x.d_big = (x.d_big + jnp.sum(through, 1, keepdims=True)
                   - _column(jnp.sum(through, 0, keepdims=True)))


def _channel_matrices_grad(xs):
    """The gradient of ``_channel_matrices``' ``A`` and ``B``, sub-block by
    sub-block as they were made: leaves ``k_rows``, ``q_rows``, ``k_cols``
    [C, dk] and adds the decays' part to ``x.d_big`` [C, dk]."""
    c, dk = xs[0].qf.shape
    n_sub = c // SUB
    lane, column = _iota((SUB, c), 1), _iota((SUB, 1), 0)
    for x in xs:
        x.k_rows, x.q_rows, x.k_diag = [], [], []
        x.k_cols = jnp.zeros_like(x.kf)
    # A and B: the gradient of k as a row of either (``k_rows``), of q as a
    # row of B, of k as a column of both (``k_cols``; ``k_diag`` its part
    # from the diagonal blocks); the decays' gradient is those times k and q
    for i in range(n_sub):
        r0 = i * SUB
        for x in xs:
            big_i, k_i, q_i = (a[r0:r0 + SUB] for a in (x.big, x.kf, x.qf))
            da_i, db_i = x.d_a[r0:r0 + SUB], x.d_b[r0:r0 + SUB]   # [SUB, C]
            by_row_k = by_row_q = by_col = jnp.zeros_like(k_i)
            for s in range(SUB):
                top = s // 8 * 8                             # as the forward
                decay = jnp.exp(jnp.minimum(big_i[top:] - big_i[s:s + 1],
                                            0.0))
                keyed = decay * k_i[s:s + 1]
                a_col = da_i[top:, r0 + s:r0 + s + 1]        # rows > s
                b_col = db_i[top:, r0 + s:r0 + s + 1]        # rows >= s
                by_row_k = _under(by_row_k, top, by_row_k[top:] + a_col * keyed)
                by_row_q = _under(by_row_q, top, by_row_q[top:] + b_col * keyed)
                by_col = jnp.where(
                    column == s,
                    jnp.sum((a_col * k_i[top:] + b_col * q_i[top:]) * decay,
                            0, keepdims=True), by_col)
            x.k_rows.append(by_row_k)
            x.q_rows.append(by_row_q)
            x.k_diag.append(by_col)
        for x in xs:
            if not i:
                continue
            row, col_i = x.rows[i], x.cols[i - 1]
            zeros = jnp.zeros((c - r0, dk), _F32)
            k_col = jnp.concatenate([x.kf[:r0] * col_i, zeros], 0)
            both = jnp.concatenate(
                [jnp.where(lane < r0, x.d_a[r0:r0 + SUB], 0.0),
                 jnp.where(lane < r0, x.d_b[r0:r0 + SUB], 0.0)], 0).astype(
                     x.dt)
            rows = _mm(both, k_col.astype(x.dt)) * jnp.concatenate(
                [row, row], 0)                               # [2 SUB, dk]
            cols = _mm(both, jnp.concatenate(
                [x.kf[r0:r0 + SUB] * row, x.qf[r0:r0 + SUB] * row],
                0).astype(x.dt), _TN) * jnp.concatenate([col_i, zeros], 0)
            x.k_rows[i] = x.k_rows[i] + rows[:SUB]
            x.q_rows[i] = x.q_rows[i] + rows[SUB:]
            x.k_cols = x.k_cols + cols
            # the reference decay's own gradient: nothing in exact
            # arithmetic, but the rounded operands' products depend on it,
            # and without it their rounding would reach ``g`` at every
            # position ahead of the pair
            d_ref = (jnp.sum(cols * x.kf, 0, keepdims=True)
                     - jnp.sum(rows[:SUB] * x.kf[r0:r0 + SUB]
                               + rows[SUB:] * x.qf[r0:r0 + SUB], 0,
                               keepdims=True))
            x.d_big = x.d_big + jnp.where(_iota((c, 1), 0) == r0 - 1, d_ref,
                                          0.0)
    for x in xs:
        x.k_rows, x.q_rows = (jnp.concatenate(a, 0)
                              for a in (x.k_rows, x.q_rows))
        x.k_cols = x.k_cols + jnp.concatenate(x.k_diag, 0)
        x.d_big = (x.d_big + x.kf * (x.k_rows - x.k_cols)
                   + x.qf * x.q_rows)


def _kernel_call(kernel, operands, out_like, *, starts_out: bool = False,
                 reverse: bool = False, interpret: bool | None = None,
                 rep: int = 1):
    """One ``pallas_call`` over (batch, head blocks, chunks), the chunks
    sequential and walked from the last with ``reverse``. ``operands``: q,
    k [B, T, H / rep * dk] (``rep`` value heads read one key head: a
    step's block of q and k holds its value heads' key heads, through the
    same index map), v [B, T, H * dv], g [B, T, H * dk] or, ONE decay a
    head, rows as beta's, beta's rows, then (backward) the chunk-start
    states and d_o; ``out_like``: arrays whose shapes and dtypes the
    outputs take, blocked as the operand of that shape is; ``starts_out``
    adds the chunk-start states, [B, T / CHUNK, H, dv, dk] float32.
    ``interpret`` None: as the attention kernels decide it (compiled on a
    TPU, interpreted on the CPU, refused anywhere else)."""
    from ray_tpu.ops.attention import _interpret

    if interpret is None:
        interpret = _interpret()
    return _launch(kernel, tuple(operands),
                   tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for a in out_like), starts_out, reverse, interpret,
                   rep)


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4, 5, 6))
def _launch(kernel, operands, out_shape, starts_out, reverse, interpret,
            rep=1):
    """``_kernel_call`` behind a ``jax.jit`` of its own, for the time a
    step takes to TRACE: a kernel's body is some ten thousand equations,
    seconds to trace and to lower, and a train step calls each kernel in
    every KDA layer and again wherever ``jax.checkpoint`` and the custom
    gradient run the rule once more. Under one jitted function, the same
    kernel at the same shapes is traced once a process and lowered once a
    module; XLA inlines the calls, and an instruction's ``op_name`` still
    carries the scopes of the call it came from."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ray_tpu.ops.attention import _FLASH_VMEM_MOST

    q, v, beta = operands[0], operands[2], operands[4]
    b, t = q.shape[:2]
    heads, n = beta.shape[-2], t // CHUNK
    h = beta.shape[1] * heads
    dk, dv = q.shape[-1] * rep // h, v.shape[-1] // h
    at = (lambda j: n - 1 - j) if reverse else (lambda j: j)
    starts_shape = (b, n, h, dv, dk)

    def spec(a):
        if a.shape == starts_shape:
            return pl.BlockSpec((1, 1, heads, dv, dk),
                                lambda i, hb, j: (i, at(j), hb, 0, 0))
        if a.ndim == 5:                                 # beta's rows
            return pl.BlockSpec((1, 1, 1, heads, CHUNK),
                                lambda i, hb, j: (i, hb, at(j), 0, 0))
        return pl.BlockSpec((1, CHUNK, a.shape[-1] // h * heads),
                            lambda i, hb, j: (i, at(j), hb))

    out_shape = list(out_shape)
    if starts_out:
        out_shape.append(jax.ShapeDtypeStruct(starts_shape, jnp.float32))
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, dk=dk, dv=dv, rep=rep),
        grid=(b, h // heads, n),
        in_specs=[spec(a) for a in operands],
        out_specs=[spec(a) for a in out_shape], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM_MOST),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel_rule(q, k, v, g, beta, rep):
    """``q``, ``k`` [B, T, H / rep * dk], ``v`` [B, T, H * dv], ``g``
    float32 [B, T, H * dk] or rows as ``beta``'s, ``beta`` [B, H / heads, T
    / CHUNK, heads, CHUNK], T whole chunks -> o [B, T, H * dv]."""
    return _kernel_call(_delta_fwd_kernel, (q, k, v, g, beta), [v],
                        rep=rep)[0]


def _kernel_rule_fwd(q, k, v, g, beta, rep):
    o, starts = _kernel_call(_delta_fwd_kernel, (q, k, v, g, beta), [v],
                             starts_out=True, rep=rep)
    return o, (q, k, v, g, beta, starts)


def _kernel_rule_bwd(rep, kept, d_o):
    return tuple(_kernel_call(_delta_bwd_kernel, (*kept, d_o), kept[:5],
                              reverse=True, rep=rep))


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


_KERNEL_WIDTH = 128     # dk = dv: ONE 128-lane tile a head


def _takes_kernels(q, v) -> bool:
    """Whether the Pallas kernels run a call whose operands come by heads,
    ``q`` [.., dk] and ``v`` [.., dv] (``_on_one_tpu``)."""
    return _on_one_tpu(q, q.shape[-1], v.shape[-1])


def _on_one_tpu(a, dk: int, dv: int) -> bool:
    """Whether the Pallas kernels (the delta rule's and the convolution
    chains': one rule for the whole mixer) run a call, from what can be
    seen of it: heads of ``_KERNEL_WIDTH``, the one width the kernels were
    measured at and their four-head step's live set fits the 32-MiB
    ceiling at (a wider head would fail in Mosaic where the scan runs);
    one TPU chip under the operand ``a`` (``_one_tpu``)."""
    return dk == dv == _KERNEL_WIDTH and _one_tpu(a)


def _one_tpu(a) -> bool:
    """A TPU, and no mesh over the operand ``a`` (``_mesh_over``): where
    any Pallas kernel of a linear mixer may run. The state-space mixer's
    three ops ask this and their own shapes (``flat_conv_silu``,
    ``state_space._takes_kernels``, ``state_space._norm_takes_kernels``)."""
    return jax.devices()[0].platform == "tpu" and not _mesh_over(a)


def _mesh_over(a) -> bool:
    """Whether more than one device may lie under ``a``: Mosaic refuses a
    call that XLA would have to partition, and the state runs along T,
    which no kernel of a shard could carry. From what says it, in order: a
    concrete array's own sharding; the mesh in force where the call is
    traced (``jax.set_mesh``; a ``shard_map`` all of whose axes are manual
    hands the kernel its shard, as does a mesh of one device). Traced with
    neither, the devices the jitted function will run on cannot be seen
    from here (``forward(mesh=...)`` puts its mesh in shardings, not in
    force): the process's own count stands for them, and where that alone
    keeps the kernels out the choice is logged, once."""
    if not isinstance(a, jax.core.Tracer):
        sharding = getattr(a, "sharding", None)     # None: the host's array
        return sharding is not None and len(sharding.device_set) > 1
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return mesh.size > 1 and set(mesh.manual_axes) != set(mesh.axis_names)
    devices = len(jax.devices())
    if devices > 1:
        _log_once(
            f"gated_delta_rule: traced under no mesh in a process that holds "
            f"{devices} devices, so the XLA scan runs and not the Pallas "
            f"kernels (a mesh may lie over the operands); for one device's "
            f"work trace it under jax.set_mesh of that device's mesh or "
            f"inside a shard_map")
    return devices > 1


@functools.cache
def _log_once(message: str) -> None:
    logger.warning(message)


# -- a convolution chain, as Pallas kernels ---------------------------------------
#
# One grid step is ``_CONV_TOKENS`` tokens of ``_CONV_LANES`` lanes (whole
# heads) of a flat [B, T, H * d] array: tokens in the sublanes, as the delta
# rule's kernels read their operands. A tile's K - 1 rows of HALO, the
# tokens just ahead of it, come as a second block of the same array: its
# last ``_CONV_HALO`` rows before the tile (zeros ahead of the row's first
# tile), so no tile waits for another and nothing is padded in HBM. Inside
# a step the chain runs a head's 128 lanes and ``_CONV_ROWS`` tokens at a
# time, start to end, in Python loops, which is unrolled code: a whole
# tile's chain at once goes through VMEM once an operation, and a
# ``fori_loop`` waits out every block's lane sums and rsqrt (both measured:
# PERF.md section 6, PR 49). The backward walks the tiles, and a tile's
# blocks, from the LAST: ``dx`` of a token reads the pre-activation's
# gradient of the K - 1 tokens behind it, which the next block is handed
# and a scratch carries from tile to tile (``dz`` is made once a token); ``dw`` [B, K, C] float32 sums in
# its output block, which stays in VMEM along the token axis. All
# arithmetic float32, ONE rounding, at the store. A chain WITH A BIAS (a
# state-space layer's, ``flat_conv_silu``) is handed one more row beside the
# taps, [1, C] float32, added ahead of the SiLU in both kernels; its
# gradient is one more row of ``dw``'s block. A chain that has none hands
# no operand in and traces no arithmetic for one.

_CONV_TOKENS = 1024
_CONV_LANES = 512
_CONV_ROWS = 128
_CONV_HALO = 8          # rows of halo a tile is given: K - 1 at most
_CONV_HALO_BLOCK = 16   # rows of the block that brings them: a whole tile
                        # of bfloat16's (16, 128) tiling


def _halo(ref, at: int, cols):
    """The ``_CONV_HALO`` rows that end the ``_CONV_HALO_BLOCK`` rows of
    ``ref`` from row ``at``, float32."""
    block = ref[0, at:at + _CONV_HALO_BLOCK, cols].astype(_F32)
    return block[_CONV_HALO_BLOCK - _CONV_HALO:]


def _taps(ext, w, rows: int):
    """(the convolution ``z`` [rows, d] of ``ext`` [_CONV_HALO + rows, d],
    the rows under the halo ahead of them: ``_conv``'s sums in ``_conv``'s
    order; the rows each tap read)."""
    taps = w.shape[0]
    read = [ext[_CONV_HALO - taps + 1 + j:][:rows] for j in range(taps)]
    return sum(x * w[j:j + 1] for j, x in enumerate(read)), read


def _unit_scale(s):
    return jax.lax.rsqrt(jnp.sum(s * s, -1, keepdims=True) + L2_EPS)


def _conv_blocks(x_ref, d: int = _KERNEL_WIDTH):
    """(tokens a block of the loop inside a tile: ``_CONV_ROWS``, or a
    ``_CONV_HALO_BLOCK`` where a short row's tile is no whole number of
    those; every block's first row) of a tile. Over ``d`` lanes at a time
    and not 128, as many fewer tokens, so that a block stays the same
    vector registers."""
    tokens = x_ref.shape[1]
    rows = _CONV_ROWS if tokens % _CONV_ROWS == 0 else _CONV_HALO_BLOCK
    rows = max(rows * _KERNEL_WIDTH // d, _CONV_HALO_BLOCK)
    return rows, range(0, tokens, rows)


def _conv_fwd_kernel(x_ref, ahead_ref, w_ref, *rest, norm: bool, d: int):
    import jax.experimental.pallas as pl

    *bias_ref, y_ref = rest         # the bias row [1, lanes] only if given
    rows, starts = _conv_blocks(x_ref, d)
    for at in range(0, x_ref.shape[2], d):
        cols = slice(at, at + d)
        w = w_ref[:, cols]
        ahead = jnp.where(pl.program_id(2) == 0, 0.0,
                          _halo(ahead_ref, 0, cols))
        for r0 in starts:
            here = x_ref[0, r0:r0 + rows, cols].astype(_F32)
            z, _ = _taps(jnp.concatenate([ahead, here], 0), w, rows)
            if bias_ref:
                z = z + bias_ref[0][:, cols]
            y = jax.nn.silu(z)
            if norm:
                y = y * _unit_scale(y)
            y_ref[0, r0:r0 + rows, cols] = y.astype(y_ref.dtype)
            ahead = here[rows - _CONV_HALO:]


def _conv_bwd_kernel(x_ref, ahead_ref, w_ref, *rest, norm: bool, d: int):
    """``dw_ref`` [1, K, lanes], with a bias [1, K + 1, lanes]: the bias'
    gradient, ``dz`` summed as the taps' are, is its last row."""
    import jax.experimental.pallas as pl

    *bias_ref, dy_ref, dx_ref, dw_ref, behind = rest

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        behind[...] = jnp.zeros_like(behind)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    rows, starts = _conv_blocks(x_ref, d)
    taps = w_ref.shape[0]
    first = pl.program_id(2) == pl.num_programs(2) - 1      # of its row
    for at in range(0, x_ref.shape[2], d):
        cols = slice(at, at + d)
        w = w_ref[:, cols]
        after = behind[:, cols]
        sums = [jnp.zeros((8, d), _F32)] * dw_ref.shape[1]
        for r0 in reversed(starts):
            here = x_ref[0, r0:r0 + rows, cols].astype(_F32)
            ahead = (_halo(x_ref, r0 - _CONV_HALO_BLOCK, cols) if r0 else
                     jnp.where(first, 0.0, _halo(ahead_ref, 0, cols)))
            z, read = _taps(jnp.concatenate([ahead, here], 0), w, rows)
            if bias_ref:
                z = z + bias_ref[0][:, cols]
            gate = jax.nn.sigmoid(z)
            ds = dy_ref[0, r0:r0 + rows, cols].astype(_F32)
            if norm:
                # y = s / |s|: ds = (dy - y (dy . y)) / |s|
                s = z * gate
                scale = _unit_scale(s)
                y = s * scale
                ds = scale * (ds - y * jnp.sum(ds * y, -1, keepdims=True))
            dz = ds * gate * (1.0 + z * (1.0 - gate))
            under = jnp.concatenate([dz, after], 0)
            dx_ref[0, r0:r0 + rows, cols] = sum(
                under[taps - 1 - j:][:rows] * w[j:j + 1]
                for j in range(taps)).astype(dx_ref.dtype)
            # eight rows' sums a tap: whole registers added, no shuffles
            # (the bias' row reads ones)
            sums = [total + sum((dz * x)[r:r + 8] for r in range(0, rows, 8))
                    for total, x in zip(sums, read + [1.0] * len(bias_ref))]
            after = dz[:_CONV_HALO]
        behind[:, cols] = after
        dw_ref[0, :, cols] += jnp.concatenate(
            [jnp.sum(total, 0, keepdims=True) for total in sums], 0)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _conv_launch(norm, d, tile, interpret, x, w, bias=None, dy=None):
    """One chain's forward (``dy`` None: -> y) or backward (-> dx, dw [B,
    K, C] float32, with a bias [B, K + 1, C]: its gradient the last row)
    over (batch, lane blocks, token tiles), behind a ``jax.jit`` of its own
    as ``_launch`` is: a step traces each variant once. ``x`` [B, T, C] and
    ``tile`` (tokens, lanes) as ``_conv_call`` gives them; ``w`` [K, C],
    ``bias`` [1, C] or None, float32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ray_tpu.ops.attention import _FLASH_VMEM_MOST

    b, t, c = x.shape
    rows, lanes = tile
    n, per = t // rows, rows // _CONV_HALO_BLOCK
    at = (lambda j: n - 1 - j) if dy is not None else (lambda j: j)
    block = pl.BlockSpec((1, rows, lanes), lambda i, l, j: (i, at(j), l))
    ahead = pl.BlockSpec(
        (1, _CONV_HALO_BLOCK, lanes),
        lambda i, l, j: (i, jnp.maximum(at(j) * per - 1, 0), l))
    taps = pl.BlockSpec((w.shape[0], lanes), lambda i, l, j: (0, l))
    given = [] if bias is None else [bias]
    row = [pl.BlockSpec((1, lanes), lambda i, l, j: (0, l))] * len(given)
    like = jax.ShapeDtypeStruct(x.shape, x.dtype)
    params = dict(
        grid=(b, c // lanes, n), interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM_MOST))
    if dy is None:
        return pl.pallas_call(
            functools.partial(_conv_fwd_kernel, norm=norm, d=d),
            in_specs=[block, ahead, taps, *row], out_specs=block,
            out_shape=like, **params)(x, x, w, *given)
    sums = w.shape[0] + len(given)
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, norm=norm, d=d),
        in_specs=[block, ahead, taps, *row, block],
        out_specs=[block, pl.BlockSpec((1, sums, lanes),
                                       lambda i, l, j: (i, 0, l))],
        out_shape=[like, jax.ShapeDtypeStruct((b, sums, c), _F32)],
        scratch_shapes=[pltpu.VMEM((_CONV_HALO, lanes), _F32)],
        **params)(x, x, w, *given, dy)


def _conv_tokens(t: int) -> int:
    """Tokens a grid step: ``_CONV_TOKENS``, or a shorter row's whole
    length in ``_CONV_HALO_BLOCK``s."""
    return min(_CONV_TOKENS, -(-t // _CONV_HALO_BLOCK) * _CONV_HALO_BLOCK)


def _conv_tile(x) -> tuple[int, int]:
    """(tokens, lanes) a grid step of ``x`` [B, T, C]: T whole
    ``_conv_tokens``, C whole heads of 128 lanes."""
    return (_conv_tokens(x.shape[1]),
            next(n for n in (_CONV_LANES, 256, 128) if x.shape[2] % n == 0))


def _conv_call(norm: bool, d: int, x, w, bias=None, dy=None):
    """``_conv_launch`` with the tile the operands take (``_conv_tile``),
    interpreted where the attention kernels are."""
    from ray_tpu.ops.attention import _interpret

    return _conv_launch(norm, d, _conv_tile(x), _interpret(), x, w, bias, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_chain(x, w, bias, norm, d):
    """``x`` [B, T, C], T whole tiles; ``w`` [K, C], ``bias`` [1, C] or
    None, float32 -> the chain's ``y`` [B, T, C] in ``x``'s dtype. The
    backward keeps the operands and makes the pre-activation again inside
    its one pass."""
    return _conv_call(norm, d, x, w, bias)


def _kernel_chain_fwd(x, w, bias, norm, d):
    return _conv_call(norm, d, x, w, bias), (x, w, bias)


def _kernel_chain_bwd(norm, d, kept, dy):
    dx, dw = _conv_call(norm, d, *kept, dy)
    dw = dw.sum(0)
    if kept[2] is None:
        return dx, dw, None
    return dx, dw[:-1], dw[-1:]


_kernel_chain.defvjp(_kernel_chain_fwd, _kernel_chain_bwd)


def _chain_kernels(x, w, norm: bool, bias=None):
    """``_chain`` through the kernels: T padded behind the row to whole
    tiles (zeros: a causal chain's real tokens never read them, and their
    ``dy`` is zero), the taps viewed [K, H * d] in float32 and the bias
    [1, C]. A chain with no heads (taps [K, C]: no l2 norm, so no statistic
    to keep inside a head) runs a grid step's lanes whole and as many fewer
    tokens at a time (``_conv_blocks``): a quarter of the eight-row slices
    to trace for the same arithmetic."""
    t = x.shape[1]
    pad = -t % _conv_tokens(t)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    if bias is not None:
        bias = bias.astype(_F32).reshape(1, -1)
    y = _kernel_chain(x, w.astype(_F32).reshape(w.shape[0], -1), bias, norm,
                      w.shape[-1] if w.ndim == 3 else _conv_tile(x)[1])
    return y[:, :t]


# -- the gated head norm, as Pallas kernels -----------------------------------------
#
# The chains' tiling and the chains' loops: a grid step is ``_CONV_TOKENS``
# tokens of ``_CONV_LANES`` lanes (whole heads) of the flat [B, T, H * dv]
# arrays, and inside it a head's 128 lanes and ``_CONV_ROWS`` tokens run
# start to end at a time. A token reads nothing of another, so there is no
# halo and no order among the tiles. A head's mean of squares is a lane sum
# in float32. The gate's pre-activation is read (``z``) or made on the MXU
# from the block's [rows, r] of ``low`` and one head's [r, 128] of ``g_b``,
# float32 sums; the backward makes the statistic and the gate again and
# keeps nothing of the forward's. ``d_weight`` [B, 8, C] float32 sums in its
# output block along the token axis, eight rows of whole registers (the
# chains' ``dw``); what is left of the sum (batch, the eight rows, the
# heads that share the weight) is XLA's, over a few kilobytes. All
# arithmetic float32, ONE rounding, at each store.
#
# **The same two kernels are a state-space layer's gated GROUP norm**
# (``state_space.gated_group_norm``: ``rmsnorm_group(y silu(z)) weight``)
# where the launch is handed a ``group``: the statistic spans the group's
# lanes (512 in Nemotron-3-Nano: a grid step's lanes hold whole groups),
# the gate is AHEAD of the norm and the weight is [1, C], a channel each.
# The loop then runs a group's lanes and as many fewer tokens at a time
# (``_conv_blocks``: 32 x 512 where a head is 128 x 128, the same vector
# registers), so the statistic stays ONE lane sum and nothing is walked
# twice. Which of the two a call is, is a static argument of the launch:
# a head norm's kernels hold nothing of the group norm's.


def _gate_parts(source, rows, cols):
    """(the output gate's pre-activation ``x``, ``sigmoid(x)``) float32
    [rows, d] for a block's rows and one head's lanes: ``z`` as it is read
    where ``source`` is ``(z_ref,)``, ``low @ g_b`` where it is ``(low_ref,
    g_b_ref)``."""
    if len(source) == 1:
        x = source[0][0, rows, cols].astype(_F32)
    else:
        low_ref, g_b_ref = source
        x = jnp.dot(low_ref[0, rows, :], g_b_ref[:, cols],
                    preferred_element_type=_F32)
    return x, jax.nn.sigmoid(x)


def _norm_fwd_kernel(o_ref, *refs, eps: float, d: int, ahead: bool):
    *source, w_ref, y_ref = refs
    silu = len(source) == 1
    size, starts = _conv_blocks(o_ref, d)
    w = w_ref[...]
    for at in range(0, o_ref.shape[2], d):
        cols = slice(at, at + d)
        for r0 in starts:
            rows = slice(r0, r0 + size)
            of = o_ref[0, rows, cols].astype(_F32)
            x, s = _gate_parts(source, rows, cols)
            if ahead:
                of = of * (x * s)
            scale = jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + eps)
            y_ref[0, rows, cols] = (
                of * scale * w[:, cols] if ahead else
                of * scale * w * (x * s if silu else s)).astype(y_ref.dtype)


def _norm_bwd_kernel(o_ref, *refs, eps: float, d: int, ahead: bool):
    import jax.experimental.pallas as pl

    *source, w_ref, dy_ref, do_ref, dx_ref, dw_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    silu = len(source) == 1
    size, starts = _conv_blocks(o_ref, d)
    w = w_ref[...]
    for at in range(0, o_ref.shape[2], d):
        cols = slice(at, at + d)
        total = jnp.zeros((8, d), _F32)
        for r0 in starts:
            rows = slice(r0, r0 + size)
            of = o_ref[0, rows, cols].astype(_F32)
            x, s = _gate_parts(source, rows, cols)
            gate, slope = ((x * s, s * (1.0 + x * (1.0 - s))) if silu else
                           (s, s * (1.0 - s)))
            normed = of * gate if ahead else of
            scale = jax.lax.rsqrt(
                jnp.mean(normed * normed, -1, keepdims=True) + eps)
            n = normed * scale
            dy = dy_ref[0, rows, cols].astype(_F32)
            by_n = dy * n                   # y = n w gate, or y = n w
            if not ahead:
                dx_ref[0, rows, cols] = (by_n * w * slope).astype(dx_ref.dtype)
            # n = v / rms(v), v what is normed: d_v = (dn - n mean(dn n)) /
            # rms(v)
            dn = dy * w[:, cols] if ahead else dy * w * gate
            d_normed = scale * (dn - n * jnp.mean(dn * n, -1, keepdims=True))
            if ahead:                       # v = o gate
                dx_ref[0, rows, cols] = (d_normed * of * slope
                                         ).astype(dx_ref.dtype)
                d_normed = d_normed * gate
            do_ref[0, rows, cols] = d_normed.astype(do_ref.dtype)
            by_gate = by_n if ahead else by_n * gate
            total = total + sum(by_gate[r:r + 8] for r in range(0, size, 8))
        dw_ref[0, :, cols] += total


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _norm_launch(eps, group, tile, interpret, o, source, weight, dy=None):
    """The head norm's forward (``dy`` None: -> y) or backward (-> d_o, the
    pre-activation's gradient in the source's dtype, d_weight [B, 8, C]
    float32) over (batch, lane blocks, token tiles), behind a ``jax.jit``
    of its own as ``_conv_launch`` is. ``o`` [B, T, C] and ``tile``
    (tokens, lanes) as ``_norm_call`` gives them; ``source`` ``(z,)`` or
    ``(low, g_b)``; ``weight`` [1, d] float32, the heads'. **A ``group``
    (lanes; 0: none) makes it the state-space layer's gated GROUP norm**
    (``state_space.gated_group_norm``): the statistic over ``group`` lanes,
    the gate AHEAD of the norm, ``weight`` [1, C], a channel each."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ray_tpu.ops.attention import _FLASH_VMEM_MOST

    b, t, c = o.shape
    rows, lanes = tile
    block = pl.BlockSpec((1, rows, lanes), lambda i, l, j: (i, j, l))
    gate = [block] if len(source) == 1 else [
        pl.BlockSpec((1, rows, source[0].shape[2]), lambda i, l, j: (i, j, 0)),
        pl.BlockSpec((source[1].shape[0], lanes), lambda i, l, j: (0, l))]
    shared = (pl.BlockSpec((1, lanes), lambda i, l, j: (0, l)) if group else
              pl.BlockSpec(weight.shape, lambda i, l, j: (0, 0)))
    like = jax.ShapeDtypeStruct(o.shape, o.dtype)
    params = dict(
        grid=(b, c // lanes, t // rows), interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM_MOST))
    kernel = dict(eps=eps, d=group or weight.shape[1], ahead=bool(group))
    if dy is None:
        return pl.pallas_call(
            functools.partial(_norm_fwd_kernel, **kernel),
            in_specs=[block, *gate, shared], out_specs=block, out_shape=like,
            **params)(o, *source, weight)
    return pl.pallas_call(
        functools.partial(_norm_bwd_kernel, **kernel),
        in_specs=[block, *gate, shared, block],
        out_specs=[block, block,
                   pl.BlockSpec((1, 8, lanes), lambda i, l, j: (i, 0, l))],
        out_shape=[like, jax.ShapeDtypeStruct(o.shape, source[0].dtype),
                   jax.ShapeDtypeStruct((b, 8, c), _F32)],
        **params)(o, *source, weight, dy)


def _norm_call(eps: float, group: int, o, source, weight, dy=None):
    """``_norm_launch`` with the tile the operands take, the chains'
    (``_conv_tile``), interpreted where the attention kernels are."""
    from ray_tpu.ops.attention import _interpret

    return _norm_launch(eps, group, _conv_tile(o), _interpret(), o, source,
                        weight, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_head_norm(o, source, weight, eps, group):
    """``o`` [B, T, C], T whole tiles; ``source`` ``(z,)`` or ``(low,
    g_b)``; ``weight`` [1, d] float32, or with a ``group`` [1, C]
    (``_norm_launch``) -> the gated norm [B, T, C] in ``o``'s dtype. The
    backward keeps the operands and makes the statistic and the gate again
    inside its one pass."""
    return _norm_call(eps, group, o, source, weight)


def _kernel_head_norm_fwd(o, source, weight, eps, group):
    return _norm_call(eps, group, o, source, weight), (o, source, weight)


def _kernel_head_norm_bwd(eps, group, kept, dy):
    """``dx``, the pre-activation's gradient, leaves the kernel rounded
    once: it IS ``dz``, and the low-rank map's two small matmuls, XLA's,
    read it as they read XLA's own rounding pass of the float32 one.
    ``d_g_b`` sums over the tokens, the MAJOR dimension of both operands:
    asked for in float32 XLA takes them as they lie; asked for in bfloat16
    it first writes ``dx`` transposed, [H * dv, B T], 0.40 ms of a 0.81-ms
    backward at [1, 16384, 4096] (PERF.md section 6, PR 54)."""
    _, source, weight = kept
    d_o, dx, dw = _norm_call(eps, group, *kept, dy)
    d_source = (dx,) if len(source) == 1 else (
        jnp.einsum("btc,rc->btr", dx, source[1]),
        jnp.einsum("btr,btc->rc", source[0], dx,
                   preferred_element_type=_F32).astype(source[1].dtype))
    return d_o, d_source, dw.reshape(-1, *weight.shape).sum(0)


_kernel_head_norm.defvjp(_kernel_head_norm_fwd, _kernel_head_norm_bwd)


def _head_norm_kernels(o, source, weight, eps: float, group: int = 0):
    """``_head_norm`` through the kernels: ``o`` viewed flat, T padded
    behind the row to whole tiles as ``_chain_kernels`` pads it (zeros: no
    token reads another, a zero ``o`` norms to zero, and their ``dy`` is
    zero), the weight [1, dv] in float32. With a ``group`` the state-space
    layer's gated group norm of a flat ``o`` (``_norm_launch``), the weight
    [1, C]."""
    b, t = o.shape[:2]
    flat, (by_token, *rest) = o.reshape(b, t, -1), source   # z, or low
    pad = -t % _conv_tokens(t)
    if pad:
        flat, by_token = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                          for a in (flat, by_token))
    y = _kernel_head_norm(flat, (by_token, *rest),
                          weight.astype(_F32).reshape(1, -1), eps, group)
    return y[:, :t].reshape(o.shape)


def _by_kernels(q, k, v, g, beta):
    """``gated_delta_rule`` through the kernels: operands flat, [B, T, H *
    d], or by heads as they come (``flat`` does nothing to a flat one, so
    what ``conv_silu`` and ``gates`` make is handed on as it is), T padded
    to whole chunks, the heads in blocks of ``_KERNEL_HEADS`` where they
    come in fours. ``g`` shaped as ``beta`` (ONE decay a head) goes as
    rows, as ``beta`` does. Fewer key heads than value heads: a step's
    heads bring their key heads' lanes, where those are whole key heads;
    where a key head serves more value heads than a step holds, q and k
    are repeated ahead of the kernels."""
    b, t, h = beta.shape
    pad = -t % CHUNK
    rep = h // q.shape[2] if q.ndim == 4 else 1
    blocks = [n for n in (_KERNEL_HEADS, 2, 1) if h % n == 0]
    if not any(n % rep == 0 for n in blocks):
        q, k, rep = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), 1
    heads = next(n for n in blocks if n % rep == 0)

    def flat(a):
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape(b, t + pad, -1)

    def rows(a):
        return jnp.transpose(
            flat(a.astype(jnp.float32)).reshape(
                b, (t + pad) // CHUNK, CHUNK, h // heads, heads),
            (0, 3, 1, 4, 2))

    by_head = g.shape == beta.shape
    o = _kernel_rule(flat(q), flat(k), flat(v),
                     rows(g) if by_head else flat(g.astype(jnp.float32)),
                     rows(beta), rep)
    return o.reshape(b, t + pad, h, -1)[:, :t]


def gated_delta_rule(q, k, v, g, beta):
    """The gated delta rule in chunks (module docstring): ``q``, ``k`` [B,
    T, H, dk], ``v`` [B, T, H, dv], ``g`` float32 (the log-decay per
    channel, <= 0), ``beta`` [B, T, H] -> ``o`` [B, T, H, dv] in ``q``'s
    dtype, ``S_0 = 0``. Every one of ``q``, ``k``, ``v``, ``g`` comes by
    heads or FLAT, [B, T, H * d], what ``conv_silu`` and ``gates`` make and
    the kernels read (they take it with no reshape, transpose or copy, and
    its gradient goes back flat); its RANK says which, ``beta`` how many
    heads. **``g`` shaped as ``beta``, [B, T, H], is ONE log-decay a head
    and token** (Gated DeltaNet; ``head_gates``), every channel's. **``q``
    and ``k`` by heads may have FEWER heads than ``v``**, [B, T, H / r,
    dk]: value head ``j`` reads key head ``j // r`` (a flat q or k has
    ``H`` heads). A ``T`` that is no whole number of chunks is padded
    behind the row with tokens that write nothing (``beta`` = 0) and
    forget nothing (``g`` = 0): no real token sees them. Differentiable in
    all five operands. The Pallas kernels where ``_on_one_tpu`` finds
    their case, else the scan in plain XLA."""
    h = beta.shape[-1]
    dk = q.shape[-1] // (h if q.ndim == 3 else 1)
    dv = v.shape[-1] // (h if v.ndim == 3 else 1)
    if _on_one_tpu(q, dk, dv):
        return _by_kernels(q, k, v, g, beta)
    return _by_scan(q, k, v, g, beta)


def _by_scan(q, k, v, g, beta):
    b, t, h = beta.shape
    pad = -t % CHUNK
    if q.ndim == 4 and q.shape[2] != h:    # a key head to h / its heads
        q, k = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (q, k))
    if g.shape == beta.shape:               # ONE decay a head: every channel's
        g = jnp.repeat(g, q.shape[-1] // (h if q.ndim == 3 else 1), axis=2)
    # a flat operand by heads: the scan is chunk-major
    q, k, v, g = (a.reshape(b, t, h, -1) for a in (q, k, v, g))

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
