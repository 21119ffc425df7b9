"""Attention ops: reference, blockwise (flash-style), and Pallas TPU kernel.

The reference framework ships no attention kernels at all — attention
lives inside vLLM/torch models it orchestrates (reference delegates TP/PP
to vLLM via engine kwargs, llm/_internal/batch/stages/
vllm_engine_stage.py:646-647). A TPU-native framework owns this layer:
the MXU wants large fused QK^T/PV matmuls, and HBM wants the O(T^2)
scores matrix never materialized.

Shapes follow [batch, seq, heads, head_dim] throughout.

Four tiers:
  - ``dot_product_attention`` — O(T^2)-memory reference; ground truth in
    tests, what serving calls with cached keys, and the fallback for
    odd shapes.
  - ``causal_blocked_attention`` — the same exact softmax over
    materialised scores, computed in query blocks against the key PREFIX
    each block may see, so the masked upper triangle is mostly never
    computed; what ``impl="auto"`` takes for causal self-attention at
    T <= 1024 (the training path of both benchmark cells).
  - ``blockwise_attention`` — online-softmax lax.scan over key blocks:
    O(T) memory, fully differentiable, XLA-fusable; what ``auto`` takes
    above 1024 keys where the kernel does not apply, and the step ring
    attention is built from.
  - ``flash_attention`` — Pallas TPU kernels (interpret-mode on CPU):
    a custom_vjp of a forward kernel and ONE backward kernel that
    rebuilds a tile's probabilities from the saved logsumexp once and
    feeds dq, dk and dv from them (two kernels, dq and dk / dv, where a
    head's dq does not fit in VMEM), so training through it stays O(T)
    memory. Each kernel sizes its own tiles from the shapes
    (``_flash_tiles``); what ``auto`` takes above 1024 keys on a TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_NEG_INF = -1e30

# What the Pallas kernels' operand layout costs around them, as named
# scopes inside the model's ``attn`` / ``attn_core`` (forward, and INSIDE
# the custom gradient's backward rule): ``attn_layout`` every move between
# [B, T, H, W] and the kernels' [B * H, T, W] and the logsumexp's between
# a column and dense; ``attn_delta`` the backward's rowsum(dO * O). The
# kernels themselves need none: their ``op_name`` ends in ``pallas_call``.
# This file is one of ``models.transformer.SCOPE_FILES``.
SCOPES = ("attn_layout", "attn_delta")


def _causal_mask(q_pos, k_pos, window: int | None = None):
    """Key j is visible to query i iff ``j <= i`` and, under a sliding
    ``window``, ``i - j < window`` (the query's own position counts)."""
    d = q_pos[:, None] - k_pos[None, :]
    return d >= 0 if window is None else (d >= 0) & (d < window)


def dot_product_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                          window: int | None = None):
    """Reference attention. q: [B,Tq,H,D], k/v: [B,Tk,H,D].

    ``q_offset`` is the global position of q's first row relative to k
    (used by decode steps and by ring attention's shifted blocks).
    ``window`` (causal only) keeps the last ``window`` keys a query.

    Dtype policy (the v5e tuning that took GPT-2 124M training from 67k
    to 91k tok/s/chip): the [B,H,Tq,Tk] scores and saved softmax output
    stay in the INPUT dtype (bf16 in training — the MXU accumulates
    fp32 internally either way), while the softmax itself runs in fp32
    in-register (XLA fuses the upcast chain; only the bf16 result is
    materialized/saved for backward). fp32 inputs keep full fp32 math.
    """
    *_, d = q.shape
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = jnp.arange(k.shape[1])
        s = jnp.where(_causal_mask(q_pos, k_pos, window)[None, None], s,
                      _NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype)).astype(q.dtype)


def _causal_block_rows(t: int) -> int:
    """Rows in a query block of ``causal_blocked_attention``, from T
    alone: a quarter of T in whole 128-row tiles (so every key prefix
    ends on a lane tile), at least one tile; 0 (no blocking) where T is
    not a whole number of at least two such blocks. Four blocks (T = 512,
    1024) compute 62.5% of the T^2 score entries; the sweep of 2 / 4 / 8
    on the v5e that settled it, in both benchmark cells, is in PERF.md
    section 6, PR 25."""
    rows = max(128, t // 4 // 128 * 128)
    return rows if t > rows and t % rows == 0 else 0


def causal_blocked_attention(q, k, v, *, block_q: int,
                             window: int | None = None):
    """Exact causal self-attention (Tq == Tk, no offset) that skips the
    masked blocks: query block i multiplies its ``block_q`` rows against
    the key / value prefix ``[0 : (i+1)*block_q]`` only, a static slice,
    and masks only inside that prefix (under a ``window``, the prefix
    starts at the first key the block's first row may see). Every row's softmax runs over
    exactly the keys ``dot_product_attention`` allows it (the entries
    dropped had probability exactly 0), with the same dtype policy.
    (n+1)/2n of the T^2 entries are computed with n blocks; jax
    differentiates the loop as it stands, the gradients of k and v
    arriving as a pad-and-add of the n prefix-shaped pieces."""
    t = q.shape[1]
    if t != k.shape[1] or t % block_q:
        raise ValueError(
            f"causal_blocked_attention: Tq == Tk must divide into blocks "
            f"of {block_q} rows, got Tq={t}, Tk={k.shape[1]}")
    outs = []
    for start in range(0, t, block_q):
        end = start + block_q
        first = 0 if window is None else max(0, start - window + 1)
        outs.append(dot_product_attention(
            q[:, start:end], k[:, first:end], v[:, first:end], causal=True,
            q_offset=start - first, window=window))
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# Online-softmax building block shared by blockwise + ring attention.
# ---------------------------------------------------------------------------


def online_softmax_block(q, k, v, m, l, o, *, q_pos, k_pos, causal,
                         k_valid=None, window: int | None = None):
    """One flash step: fold key block (k, v) into accumulators (m, l, o).

    q [B,Tq,H,D]; k/v [B,Tk,H,D]; m,l [B,H,Tq]; o [B,Tq,H,D] float32.
    ``k_valid`` [Tk] masks padded keys. Masked-out scores contribute
    exactly zero probability, so fully masked blocks are no-ops (no
    -inf NaN traps).
    """
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    mask = None
    if causal:
        mask = _causal_mask(q_pos, k_pos, window)
    if k_valid is not None:
        valid = jnp.broadcast_to(k_valid[None, :], (q.shape[1], k.shape[1]))
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        mask = mask[None, None]
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _finalize(o, l):
    return o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]


def blockwise_attention(q, k, v, *, causal: bool = True, block_k: int = 512,
                        q_offset: int = 0, window: int | None = None):
    """Flash-style attention as a lax.scan over key blocks: O(T) memory,
    differentiable, MXU-friendly block matmuls. A ``window`` is a mask
    here: every key block is still visited."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_k = min(block_k, tk)
    n_blocks = (tk + block_k - 1) // block_k
    pad = n_blocks * block_k - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, n_blocks, block_k, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, block_k, h, -1).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(tq)

    def step(carry, blk):
        m, l, o = carry
        kblk, vblk, idx = blk
        k_pos = idx * block_k + jnp.arange(block_k)
        m, l, o = online_softmax_block(
            q, kblk, vblk, m, l, o, q_pos=q_pos, k_pos=k_pos, causal=causal,
            k_valid=(k_pos < tk) if pad else None, window=window,
        )
        return (m, l, o), None

    m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    o0 = jnp.zeros((b, tq, h, v.shape[-1]), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        step, (m0, l0, o0), (kb, vb, jnp.arange(n_blocks))
    )
    return _finalize(o, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU flash attention: the tiles of a grid step, the forward kernel.
# ---------------------------------------------------------------------------

# The most rows of q, and of k / v, one grid step takes, in every
# kernel: each of forward, dq and dk / dv alone on the v5e, tiles of 128
# to 2048 rows at T 1280 to 8192, head widths 64 and 128, causal and not,
# ran fastest (or within 3% of it) at the largest divisor of T up to
# 1024; only the forward at T = 1280 / 1536 wants T whole, by 10%
# (PERF.md section 6, PR 28). A 1024-tile computes 10/16 of T^2 at T = 4096 where a 256-tile
# computes 136/256, and still wins: a grid step's fixed cost and the
# refetch of k / v for every block of q outweigh the masked entries.
_FLASH_ROWS = 1024
# Score-shaped [block_q, block_k] float32 tiles a grid step keeps live
# (scores, probabilities, the mask; the backward's ``dp`` and ``ds``).
# With these the arithmetic below is 1.4 to 8 times the least limit
# under which Mosaic compiles the kernel for the v5e (256 to 2048 rows,
# widths 64 and 128, both dtypes): it counts every tile as live at once.
# "bwd" is "dkv" with dq accumulated as well: the same tiles, once.
_FLASH_SCORE_TILES = {"fwd": 3, "dq": 3, "dkv": 4, "bwd": 4}
# A quarter of a v5e core's 128 MiB, for "bwd" too: with both parts of
# kanana-2's dq for the whole head beside them ([8192, 128 + 64] float32,
# 8 MiB) 1024 x 1024 tiles come to 33.0 MiB by this arithmetic and the
# rule steps to (512, 1024), which ran the layer's backward in 38.98 ms
# where (1024, 1024) under a 40-MiB ceiling ran it in 38.99 (v5e,
# [2, 8192, 32, 128 + 64]; the two kernels 52.41; PERF.md section 6,
# PR 38): nothing to move the ceiling for.
_FLASH_VMEM_MOST = 32 * 2 ** 20
_FLASH_VMEM_LEAST = 16 * 2 ** 20     # Mosaic's own default on the v5e


def _vmem_tile(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a [rows, cols] tile in VMEM: the lanes pad to 128."""
    return rows * -(-cols // 128) * 128 * itemsize


def _flash_vmem_bytes(kernel: str, block_q: int, block_k: int, d: int,
                      dtype, dv: int | None = None, dr: int = 0,
                      tq: int = 0) -> int:
    """VMEM one grid step of ``kernel`` ("fwd", "dq", "dkv" or "bwd")
    keeps live with these tiles: the operand and output blocks, each
    twice (the pipeline fetches the next step's while this one computes),
    the float32 accumulators, and the score-shaped tiles. ``d`` is the
    width of q and k, ``dv`` of v (None: ``d``), ``dr`` of the shared part
    of the key and of the queries' part that meets it (0: none). ``tq``
    ("bwd" only): the rows of a head, all of whose dq the kernel keeps."""
    dv = d if dv is None else dv
    io = jnp.dtype(dtype).itemsize
    q_rows = _vmem_tile(block_q, d, io)          # q, dq
    o_rows = _vmem_tile(block_q, dv, io)         # o, g
    k_rows = _vmem_tile(block_k, d, io)          # k, dk
    v_rows = _vmem_tile(block_k, dv, io)         # v, dv
    qr_rows = _vmem_tile(block_q, dr, io) if dr else 0    # q_shared, its dq
    kr_rows = _vmem_tile(block_k, dr, io) if dr else 0    # k_shared, its dk
    column = _vmem_tile(block_q, 1, 4)           # lse, delta, m, l
    dq_acc = lambda rows: _vmem_tile(rows, d, 4) + (  # noqa: E731
        _vmem_tile(rows, dr, 4) if dr else 0)
    if kernel == "fwd":      # q, k, v -> o, lse; scratch m, l, acc
        blocks = q_rows + qr_rows + k_rows + kr_rows + v_rows + o_rows + column
        scratch = 2 * column + _vmem_tile(block_q, dv, 4)
    elif kernel == "dq":     # q, k, v, g, lse, delta -> dq; scratch acc
        blocks = (2 * (q_rows + qr_rows) + k_rows + kr_rows + v_rows + o_rows
                  + 2 * column)
        scratch = dq_acc(block_q)
    else:                    # q, k, v, g, lse, delta -> dk, dv; their accs
        blocks = (q_rows + qr_rows + o_rows + 2 * (k_rows + kr_rows + v_rows)
                  + 2 * column)
        scratch = (_vmem_tile(block_k, d, 4) + _vmem_tile(block_k, dv, 4)
                   + (_vmem_tile(block_k, dr, 4) if dr else 0))
        if kernel == "bwd":  # ... -> dq as well; its acc holds the head
            blocks += q_rows + qr_rows
            scratch += dq_acc(tq)
    scores = _FLASH_SCORE_TILES[kernel] * _vmem_tile(block_q, block_k, 4)
    return 2 * blocks + scratch + scores


def _flash_tiles(kernel: str, tq: int, tk: int, d: int, dtype,
                 dv: int | None = None, dr: int = 0):
    """(block_q, block_k) of one grid step of ``kernel``, from the
    lengths, the head widths (``_flash_vmem_bytes``) and the inputs' dtype: of the divisors of
    each length in whole 128-row tiles up to ``_FLASH_ROWS``, the
    largest pair whose grid step fits ``_FLASH_VMEM_MOST``. That is the
    largest divisor of each, but for float32 heads four times as wide as
    any preset's. None where ``tq`` or ``tk`` is not a whole number of
    128-row tiles, and for "bwd" where a head's dq does not fit beside
    the smallest tiles: the backward then takes "dq" and "dkv"."""
    def divisors(t):
        return [r for r in range(128, min(_FLASH_ROWS, t) + 1, 128)
                if t % r == 0]

    fits = [(bq, bk) for bq in divisors(tq) for bk in divisors(tk)
            if _flash_vmem_bytes(kernel, bq, bk, d, dtype, dv, dr, tq)
            <= _FLASH_VMEM_MOST]
    return max(fits, key=lambda tile: (tile[0] * tile[1], tile[1]),
               default=None)


def _flash_launch(kernel: str, q, k, block_q, block_k, v=None,
                  q_shared=None):
    """What a ``pallas_call`` of ``kernel`` is launched with: the tiles
    (the caller's, or the rule's where it gave none) and the compiler
    parameters that grant the VMEM those tiles need. ``v`` None: as wide
    as q and k."""
    from jax.experimental.pallas import tpu as pltpu

    tq, tk, d = q.shape[1], k.shape[1], q.shape[-1]
    dv = d if v is None else v.shape[-1]
    dr = 0 if q_shared is None else q_shared.shape[-1]
    if block_q is None or block_k is None:
        tiles = _flash_tiles(kernel, tq, tk, d, q.dtype, dv, dr)
        if tiles is None:
            raise ValueError(
                f"flash_attention: seq lens ({tq},{tk}) are not whole "
                f"128-row tiles; use impl='auto'")
        block_q, block_k = tiles
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    if tq % block_q or tk % block_k:
        raise ValueError(f"seq lens ({tq},{tk}) must divide blocks "
                         f"({block_q},{block_k})")
    vmem = max(_flash_vmem_bytes(kernel, block_q, block_k, d, q.dtype, dv, dr,
                                 tq), _FLASH_VMEM_LEAST)
    return block_q, block_k, pltpu.CompilerParams(vmem_limit_bytes=vmem)


def _visible_blocks(i, rows: int, cols: int, n_cols: int, before: int,
                    after: int):
    """(first, last) of the ``n_cols`` blocks of ``cols`` positions that
    hold a position some row of block ``i`` (``rows`` rows) can see, a row
    r seeing ``r - before .. r + after`` (``_window_reach``). ``i`` is a Python int
    (the grid's size) or a traced one (inside a kernel or an index map):
    the ONE rule for which tiles a causal kernel visits, windowed or not."""
    lo, hi = i * rows - before, i * rows + rows - 1 + after
    if isinstance(i, int):
        return max(lo, 0) // cols, min(hi // cols, n_cols - 1)
    return jnp.maximum(lo, 0) // cols, jnp.minimum(hi // cols, n_cols - 1)


def _flash_inner(causal, window, rows: int, cols: int, n_rows: int,
                 n_cols: int, rows_are_queries: bool):
    """The inner axis of a kernel's grid: (steps a row block takes, the
    inner block that step j of row block i reads). Not causal: every row
    block walks all ``n_cols`` blocks. Causal, with a window or none: the
    axis is only as long as the most blocks any row block can see
    (``n_cols`` at Tq == Tk with no window), step j reads the j-th of
    them, and a step past the last visible block stays on it (no new
    fetch) and computes nothing."""
    if not causal:
        return n_cols, lambda i, j: j
    reach = _window_reach(window, rows_are_queries)
    steps = max(last - first + 1 for first, last in (
        _visible_blocks(i, rows, cols, n_cols, *reach)
        for i in range(n_rows)))

    def block(i, j):
        first, last = _visible_blocks(i, rows, cols, n_cols, *reach)
        return jnp.minimum(first + j, last)

    return steps, block


def _window_reach(window, rows_are_queries: bool) -> tuple[int, int]:
    """(before, after) of ``_visible_blocks``: a query sees ``window - 1``
    keys before it and none after; a key is seen by no query before it
    and ``window - 1`` after. No window is the widest one: a reach past
    any row's end (positions stay inside int32), whatever Tq and Tk."""
    reach = 2 ** 30 if window is None else window - 1
    return (reach, 0) if rows_are_queries else (0, reach)


def _inner_block(step, row_blk, rows: int, cols: int, n_cols: int, causal,
                 window, rows_are_queries: bool):
    """Inside a kernel: (the inner block grid step ``step`` of row block
    ``row_blk`` is at, whether that step is within the row block's
    visible blocks: all of them where not causal)."""
    if not causal:
        return step, True
    first, last = _visible_blocks(row_blk, rows, cols, n_cols,
                                  *_window_reach(window, rows_are_queries))
    return first + step, first + step <= last


def _tile_is_full(q_blk, k_blk, block_q: int, block_k: int, window):
    """Every (query, key) pair of the tile is visible: its last key is at
    or before its first query, and its first key is inside the window
    (if any) of its last query."""
    full = (k_blk + 1) * block_k - 1 <= q_blk * block_q
    if window is None:
        return full
    return full & ((q_blk + 1) * block_q - 1 - k_blk * block_k < window)


def _tile_mask(q_blk, k_blk, block_q: int, block_k: int,
               window: int | None):
    q_pos = q_blk * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_blk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if window is None:
        return q_pos >= k_pos
    d = q_pos - k_pos
    return (d >= 0) & (d < window)


def _when_visible(compute, q_blk, k_blk, in_range, *, block_q, block_k,
                  causal, window):
    """Run ``compute(masked)`` for the tile (q_blk, k_blk) if it holds a
    visible pair. Causal: ``in_range`` says so (the step is within the
    row block's visible blocks), and only a tile the diagonal or the
    window's edge crosses builds a mask. Not causal: every tile, no mask."""
    import jax.experimental.pallas as pl

    if not causal:
        return compute(False)
    full = _tile_is_full(q_blk, k_blk, block_q, block_k, window)
    pl.when(in_range & full)(lambda: compute(False))
    pl.when(in_range & jnp.logical_not(full))(lambda: compute(True))


def _scores(q, k, q_shared, k_shared, scale):
    """[block_q, block_k] float32 scores of a tile: ``q k^T``, plus, where
    the heads share a part of the key, ``q_shared k_shared^T``."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    if q_shared is not None:
        s = s + jax.lax.dot_general(
            q_shared, k_shared, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return s * scale


def _flash_fwd_kernel(*refs, block_q, block_k, n_k, n_steps, causal, scale,
                      window=None, shared=False):
    import jax.experimental.pallas as pl

    if shared:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref, m_ref, l_ref,
         acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    q_blk = pl.program_id(1)
    step = pl.program_id(2)
    k_blk, in_range = _inner_block(step, q_blk, block_q, block_k, n_k,
                                   causal, window, True)

    @pl.when(step == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute(masked):
        # q [block_q, d], k [block_k, d]
        s = _scores(q_ref[0], k_ref[0], qs_ref[0] if shared else None,
                    ks_ref[0] if shared else None, scale)
        if masked:
            mask = _tile_mask(q_blk, k_blk, block_q, block_k, window)
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + p.sum(axis=1, keepdims=True)
        m_ref[:] = m_new
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * corr + pv

    # Causal: a row block's visible key blocks come first; the steps past
    # them (above the diagonal) stay on the last and compute nothing.
    _when_visible(_compute, q_blk, k_blk, in_range, block_q=block_q,
                  block_k=block_k, causal=causal, window=window)

    @pl.when(step == n_steps - 1)
    def _emit():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)
        # logsumexp row statistic: the backward kernels reconstruct the
        # NORMALIZED probabilities as exp(s - lse) without re-running the
        # online softmax. Kept [block_q, 1] — a rank-2 (bh, tq) output
        # would need a (1, block_q) block whose second-minor dim (1) the
        # Mosaic lowering rejects (must be 8-divisible or the full array
        # dim); the trailing singleton makes every block dim legal.
        lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


# The two moves below are bitcasts where XLA can lay their operand
# head-major itself, as it does a projection's OWN output: a slice, a pad
# or a concatenate between the matmul and the kernel makes each a copy
# of the whole operand (``models/mixers.py`` ``_latent_qkv``).
def _heads_flat(x):
    """[B, T, H, W] -> [B * H, T, W]: one grid row a (batch, head)."""
    b, t, h, w = x.shape
    with jax.named_scope("attn_layout"):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, w)


def _heads_back(x, b: int):
    """[B * H, T, W] -> [B, T, H, W]: what ``_heads_flat`` undoes."""
    bh, t, w = x.shape
    with jax.named_scope("attn_layout"):
        return x.reshape(b, bh // b, t, w).transpose(0, 2, 1, 3)


def _flash_scale(q, q_shared) -> float:
    """1 / sqrt of the width the scores are summed over: q's, plus the
    shared part's."""
    width = q.shape[-1] + (0 if q_shared is None else q_shared.shape[-1])
    return 1.0 / math.sqrt(width)


def _flash_forward(q, k, v, q_shared=None, k_shared=None, *, causal, block_q,
                   block_k, interpret, return_lse: bool = False, window=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    bh = b * h
    shared = q_shared is not None
    block_q, block_k, params = _flash_launch("fwd", q, k, block_q, block_k,
                                             v, q_shared)
    n_q, n_k = tq // block_q, tk // block_k
    n_steps, k_of = _flash_inner(causal, window, block_q, block_k, n_q, n_k,
                                 True)
    rows = lambda b_, i, j: (b_, i, 0)  # noqa: E731
    keys = lambda b_, i, j: (b_, k_of(i, j), 0)  # noqa: E731
    operands = [_heads_flat(q), _heads_flat(k), _heads_flat(v)]
    in_specs = [pl.BlockSpec((1, block_q, d), rows),
                pl.BlockSpec((1, block_k, d), keys),
                pl.BlockSpec((1, block_k, dv), keys)]
    if shared:      # one key part a batch row: every head reads the same
        dr = q_shared.shape[-1]
        operands += [_heads_flat(q_shared), k_shared]
        in_specs += [pl.BlockSpec((1, block_q, dr), rows),
                     pl.BlockSpec((1, block_k, dr),
                                  lambda b_, i, j: (b_ // h, k_of(i, j), 0))]

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, n_k=n_k,
        n_steps=n_steps, causal=causal, scale=_flash_scale(q, q_shared),
        window=window, shared=shared,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), rows),
            pl.BlockSpec((1, block_q, 1), rows),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(*operands)
    out = _heads_back(out, b)
    return (out, lse) if return_lse else out


# ---------------------------------------------------------------------------
# Pallas flash-attention backward kernels.
#
# Standard flash backward (FlashAttention-2 style): with the forward's
# logsumexp L and delta = rowsum(dO * O), for each (q, k) block pair
#   p  = exp(s - L)                 (normalized probabilities, recomputed)
#   dv += p^T dO
#   dp = dO V^T
#   ds = p * (dp - delta) * scale
#   dq += ds K ;  dk += ds^T Q
# ONE kernel ("bwd") wherever a head's dq fits in VMEM: the grid is
# (b, j, i), query blocks innermost, dk / dv accumulate over them in
# [block_k, d] float32 scratch, and the SAME ``ds`` adds to the rows of
# its query block in a float32 scratch that holds the whole head's dq,
# [Tq, d]. A tile's scores, mask, exponential, ``dp`` and ``ds`` are made
# once and its five matmuls run once; each element of dq leaves the
# kernel once, in the inputs' dtype, when its last key block is done.
# Where that scratch does not fit (``_flash_tiles("bwd", ...)`` is None,
# e.g. T = 65,536) two kernels, each of which rebuilds every tile: dq
# accumulates over key blocks (grid b,i,j — the forward's layout), dk/dv
# over query blocks (grid b,j,i). Both forms sum in the same order and
# give the same bits. O(T) memory; the O(T^2) probabilities exist only
# as VMEM tiles.
# ---------------------------------------------------------------------------


def _bwd_block(q, k, v, g, lse, delta, *, q_blk, k_blk, block_q, block_k,
               masked, scale, window=None, q_shared=None, k_shared=None):
    """Shared per-tile math: returns (ds [bq,bk] f32, p [bq,bk] f32).

    lse/delta arrive as [block_q, 1] column tiles (see the forward's
    _emit note on Mosaic block-shape legality) and broadcast over keys.
    Both matmuls take their operands in the INPUTS' dtype and accumulate
    in float32, as the forward kernel's do (``dot_product_attention``'s
    dtype policy); the softmax statistics, ``delta`` and the ``ds``
    arithmetic are float32. The callers cast ``p`` and ``ds`` once, to
    the inputs' dtype, for the matmuls that consume them.
    """
    s = _scores(q, k, q_shared, k_shared, scale)
    mask = None
    if masked:
        mask = _tile_mask(q_blk, k_blk, block_q, block_k, window)
        s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return ds, p


def _flash_bwd_dq_kernel(*refs, block_q, block_k, n_k, n_steps, causal,
                         scale, window=None, shared=False):
    import jax.experimental.pallas as pl

    if shared:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, dqs_ref, acc_ref, accs_ref) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
         acc_ref) = refs
    q_blk = pl.program_id(1)
    step = pl.program_id(2)
    k_blk, in_range = _inner_block(step, q_blk, block_q, block_k, n_k,
                                   causal, window, True)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if shared:
            accs_ref[:] = jnp.zeros_like(accs_ref)

    def _compute(masked):
        ds, _ = _bwd_block(
            q_ref[0], k_ref[0], v_ref[0], g_ref[0], lse_ref[0], delta_ref[0],
            q_blk=q_blk, k_blk=k_blk, block_q=block_q, block_k=block_k,
            masked=masked, scale=scale, window=window,
            q_shared=qs_ref[0] if shared else None,
            k_shared=ks_ref[0] if shared else None)
        ds = ds.astype(k_ref.dtype)
        acc_ref[:] += jax.lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if shared:
            accs_ref[:] += jax.lax.dot_general(
                ds, ks_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _when_visible(_compute, q_blk, k_blk, in_range, block_q=block_q,
                  block_k=block_k, causal=causal, window=window)

    @pl.when(step == n_steps - 1)
    def _emit():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)
        if shared:
            dqs_ref[0] = accs_ref[:].astype(dqs_ref.dtype)


def _dq_last_key_block(q_blk, block_q: int, block_k: int, n_k: int, causal):
    """The last key block that adds to the dq of query block ``q_blk``:
    the one its last row's own key is in (causal), else the last."""
    if not causal:
        return n_k - 1
    return jnp.minimum((q_blk * block_q + block_q - 1) // block_k, n_k - 1)


def _dq_out_block(q_blk, k_blk, block_q: int, block_k: int, n_q: int,
                  n_k: int, causal):
    """The query block whose dq the "bwd" kernel's output block holds
    while key block ``k_blk`` walks query block ``q_blk``. Pallas writes
    an output block back when its index moves on, so the index only ever
    moves onto a block that is finished (``_dq_last_key_block``) during
    its stay, and never returns to one: the block being walked, held
    between the first block that key block ``k_blk`` or a later one
    finishes and the last that it or an earlier one did. Causal with
    ``block_q == block_k``: the key block's own, all along; not causal:
    block 0 until the last key block, which finishes them all."""
    def finished_before(j):
        done = jnp.minimum(j * block_k // block_q, n_q) if causal else 0
        return jnp.where(j >= n_k, n_q, done)
    blk = jnp.minimum(jnp.maximum(q_blk, finished_before(k_blk)),
                      finished_before(k_blk + 1) - 1)
    return jnp.maximum(blk, 0)


def _flash_bwd_dkv_kernel(*refs, block_q, block_k, n_q, n_steps, causal,
                          scale, window=None, shared=False, n_k=None):
    """dk and dv of key block ``program_id(1)``, summed over the query
    blocks it walks. With ``n_k`` (the "bwd" kernel) dq as well: every
    tile's ``ds`` also adds ``ds k`` to its query block's rows of a
    float32 accumulator that holds the whole head (zeroed at the head's
    first step; key blocks are the OUTER axis, so a row's dq is summed in
    ascending key blocks, the dq kernel's order), and the tile of a query
    block's last key block writes those rows out, once."""
    import jax.experimental.pallas as pl

    n_in = 8 if shared else 6
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref = refs[:6]
    qs_ref, ks_ref = refs[6:n_in] if shared else (None, None)
    rest = refs[n_in:]       # the outputs, then a float32 accumulator each
    outs, accs = rest[:len(rest) // 2], rest[len(rest) // 2:]
    n_keys = 3 if shared else 2       # dk, dv, dk_shared; then dq, dq_shared
    dk_acc, dv_acc = accs[:2]
    dks_acc = accs[2] if shared else None
    k_blk = pl.program_id(1)
    step = pl.program_id(2)
    q_blk, in_range = _inner_block(step, k_blk, block_k, block_q, n_q,
                                   causal, window, False)

    @pl.when(step == 0)
    def _init():
        for acc in accs[:n_keys]:
            acc[:] = jnp.zeros_like(acc)

    if n_k is not None:
        @pl.when((step == 0) & (k_blk == 0))
        def _init_dq():
            for acc in accs[n_keys:]:
                acc[:] = jnp.zeros_like(acc)

    def _compute(masked):
        q, g = q_ref[0], g_ref[0]
        ds, p = _bwd_block(
            q, k_ref[0], v_ref[0], g, lse_ref[0], delta_ref[0],
            q_blk=q_blk, k_blk=k_blk, block_q=block_q, block_k=block_k,
            masked=masked, scale=scale, window=window,
            q_shared=qs_ref[0] if shared else None,
            k_shared=ks_ref[0] if shared else None)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = ds.astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if shared:
            dks_acc[:] += jax.lax.dot_general(
                ds, qs_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if n_k is None:
            return
        for acc, keys in zip(accs[n_keys:], (k_ref, ks_ref)):
            acc[q_blk] += jax.lax.dot_general(
                ds, keys[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(k_blk == _dq_last_key_block(q_blk, block_q, block_k, n_k,
                                             causal))
        def _emit_dq():
            for out, acc in zip(outs[n_keys:], accs[n_keys:]):
                out[0] = acc[q_blk].astype(out.dtype)

    # Causal: the walk starts at the first query block that sees this key
    # block; the steps past the last stay on it and compute nothing.
    _when_visible(_compute, q_blk, k_blk, in_range, block_q=block_q,
                  block_k=block_k, causal=causal, window=window)

    @pl.when(step == n_steps - 1)
    def _emit():
        for out, acc in zip(outs[:n_keys], accs[:n_keys]):
            out[0] = acc[:].astype(out.dtype)


def _flash_backward(q, k, v, out, lse, g, q_shared=None, k_shared=None, *,
                    causal, block_q, block_k, interpret, window=None):
    """(dq, dk, dv, dq_shared, dk_shared); the last two None with no
    shared key part. Every head's gradient of the shared part leaves the
    dk / dv kernel as its own [B * H, Tk, dr] rows and is summed over
    the heads here: the part itself is never repeated.

    ONE kernel ("bwd": the dk / dv kernel, accumulating dq as well)
    wherever a head's float32 dq fits in VMEM beside its tiles
    (``_flash_tiles("bwd", ...)``), decided from the shapes; else the two
    kernels "dq" and "dkv"."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    scale = _flash_scale(q, q_shared)
    bh = b * h
    shared = q_shared is not None
    dr = q_shared.shape[-1] if shared else 0
    one_kernel = _flash_tiles("bwd", tq, tk, d, q.dtype, dv, dr) is not None
    qf, gf, of = _heads_flat(q), _heads_flat(g), _heads_flat(out)
    kf, vf = _heads_flat(k), _heads_flat(v)
    # delta = rowsum(dO * O): one fused elementwise pass in XLA. Kept as
    # a [bh, tq, 1] column (same block-legality story as lse).
    with jax.named_scope("attn_delta"):
        delta = (gf.astype(jnp.float32) * of.astype(jnp.float32)).sum(
            -1, keepdims=True)
    operands = [qf, kf, vf, gf, lse, delta]
    if shared:
        operands += [_heads_flat(q_shared), k_shared]

    def in_specs(bq, bk, rows, keys, shared_keys):
        specs = [pl.BlockSpec((1, bq, d), rows),      # q
                 pl.BlockSpec((1, bk, d), keys),      # k
                 pl.BlockSpec((1, bk, dv), keys),     # v
                 pl.BlockSpec((1, bq, dv), rows),     # g
                 pl.BlockSpec((1, bq, 1), rows),      # lse
                 pl.BlockSpec((1, bq, 1), rows)]      # delta
        if shared:
            specs += [pl.BlockSpec((1, bq, dr), rows),
                      pl.BlockSpec((1, bk, dr), shared_keys)]
        return specs

    dq_widths = [w for w in (d, dr) if w]
    dq_shapes = [jax.ShapeDtypeStruct((bh, tq, w), q.dtype)
                 for w in dq_widths]
    if not one_kernel:      # the dq pass: grid (b, i, j), key blocks innermost
        bq, bk, params = _flash_launch("dq", q, k, block_q, block_k, v,
                                       q_shared)
        n_steps, k_of = _flash_inner(causal, window, bq, bk, tq // bq,
                                     tk // bk, True)
        rows = lambda b_, i, j: (b_, i, 0)  # noqa: E731
        keys = lambda b_, i, j: (b_, k_of(i, j), 0)  # noqa: E731
        shared_keys = lambda b_, i, j: (b_ // h, k_of(i, j), 0)  # noqa: E731
        dq = pl.pallas_call(
            functools.partial(_flash_bwd_dq_kernel, block_q=bq, block_k=bk,
                              n_k=tk // bk, n_steps=n_steps, causal=causal,
                              scale=scale, window=window, shared=shared),
            grid=(bh, tq // bq, n_steps),
            in_specs=in_specs(bq, bk, rows, keys, shared_keys),
            out_specs=[pl.BlockSpec((1, bq, w), rows) for w in dq_widths],
            out_shape=dq_shapes,
            scratch_shapes=[pltpu.VMEM((bq, w), jnp.float32)
                            for w in dq_widths],
            compiler_params=params,
            interpret=interpret,
        )(*operands)

    # The dk / dv pass: grid (b, j, i), query blocks innermost.
    bq, bk, params = _flash_launch("bwd" if one_kernel else "dkv", q, k,
                                   block_q, block_k, v, q_shared)
    n_q, n_k = tq // bq, tk // bk
    n_steps, q_of = _flash_inner(causal, window, bk, bq, n_k, n_q, False)
    rows = lambda b_, j, i: (b_, q_of(j, i), 0)  # noqa: E731
    keys = lambda b_, j, i: (b_, j, 0)  # noqa: E731
    shared_keys = lambda b_, j, i: (b_ // h, j, 0)  # noqa: E731
    key_widths = [w for w in (d, dv, dr) if w]
    out_specs = [pl.BlockSpec((1, bk, w), keys) for w in key_widths]
    out_shape = [jax.ShapeDtypeStruct((bh, tk, w), k.dtype)
                 for w in key_widths]
    scratch = [pltpu.VMEM((bk, w), jnp.float32) for w in key_widths]
    if one_kernel:          # dq as well: its outputs and accumulators follow
        dq_rows = lambda b_, j, i: (b_, _dq_out_block(  # noqa: E731
            q_of(j, i), j, bq, bk, n_q, n_k, causal), 0)
        out_specs += [pl.BlockSpec((1, bq, w), dq_rows) for w in dq_widths]
        out_shape += dq_shapes
        scratch += [pltpu.VMEM((n_q, bq, w), jnp.float32) for w in dq_widths]
    dkv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=bq, block_k=bk,
                          n_q=n_q, n_steps=n_steps, causal=causal,
                          scale=scale, window=window, shared=shared,
                          n_k=n_k if one_kernel else None),
        grid=(bh, n_k, n_steps),
        in_specs=in_specs(bq, bk, rows, keys, shared_keys),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(*operands)
    if one_kernel:
        dkv, dq = dkv[:len(key_widths)], dkv[len(key_widths):]

    grads = (_heads_back(dq[0], b), _heads_back(dkv[0], b),
             _heads_back(dkv[1], b))
    if not shared:
        return (*grads, None, None)
    dk_shared = dkv[2].reshape(b, h, tk, dr).astype(jnp.float32).sum(1)
    return (*grads, _heads_back(dq[1], b), dk_shared.astype(k_shared.dtype))


def _interpret() -> bool:
    """Whether the Pallas kernels run in the interpreter: on the CPU
    (how the tests run them) and nowhere else. On a TPU they compile
    through Mosaic; any other platform is refused, because interpreting
    there would look like a kernel that runs and never finishes."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise NotImplementedError(
        f"the Pallas flash-attention kernels run on 'tpu' (compiled) or "
        f"'cpu' (interpreted), not on platform {platform!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None, window: int | None = None,
                    q_shared=None, k_shared=None):
    """Pallas flash attention (TPU kernel; interpreter on CPU).

    Training runs the Pallas BACKWARD kernel (dq, dk and dv in one pass
    where a head's dq fits in VMEM, else a dq pass + a dk/dv pass;
    probabilities recomputed per tile from the saved logsumexp): O(T)
    memory end to end, no XLA recompute graph. The residuals are q, k, v,
    the output and the logsumexp ([B*H, Tq] float32). Under
    differentiation the last two carry the checkpoint names
    ``FLASH_OUT_NAME`` / ``FLASH_LSE_NAME``: a caller that wraps its
    layer in ``jax.checkpoint`` with a policy saving those names (the
    model's ``layer_of`` does) keeps them, one output's worth of memory a
    layer, because they are all the backward kernels need that only the
    forward kernel can make: the recompute pass then rebuilds q, k, v and
    never launches the forward kernel a second time. With no such policy
    the names do nothing; the primal (serving, any undifferentiated call)
    has none.

    Each kernel sizes its own tiles from the shapes and the dtype
    (``_flash_tiles``). ``block_q`` / ``block_k`` set the tiles of all of
    them instead: for tests, whose interpreter wants small ones.

    ``window`` (causal self-attention only) is a sliding window of that
    many keys a query, its own position among them. Every causal kernel
    walks a row block's tiles with a visible pair first and fetches
    nothing past them (``_flash_inner``; under a window the grid is
    shorter too) and builds a mask only on the tiles the diagonal or the
    window's edge crosses.

    ``v`` may be narrower or wider than q and k ([B, Tk, H, Dv]): the
    output is as wide as ``v``. ``q_shared`` [B, Tq, H, Dr] with
    ``k_shared`` [B, Tk, Dr] is a part of the key that ALL heads share
    (latent attention's rotary key): the scores are ``q k^T + q_shared
    k_shared^T`` over ``sqrt(D + Dr)``, every head reads the one
    ``k_shared`` through its block's index map, and its gradient is the
    sum of the heads'. It is never repeated to the heads in HBM.
    """
    _check_window(q, k, causal, window)
    return _flash_forward(
        q, k, v, q_shared, k_shared, causal=causal, block_q=block_q,
        block_k=block_k, interpret=_interpret(), window=window,
    )


def _check_window(q, k, causal, window):
    if window is not None and (not causal or q.shape[1] != k.shape[1]
                               or window < 1):
        raise ValueError(
            f"a sliding window is for causal self-attention (Tq == Tk) and "
            f"holds at least the query's own key, got causal={causal}, "
            f"Tq={q.shape[1]}, Tk={k.shape[1]}, window={window}")


# Checkpoint names of the kernel's output and logsumexp under
# differentiation (``flash_attention``'s docstring; saved by the policy of
# ``models/transformer.py`` ``layer_of``).
FLASH_OUT_NAME = "flash_attention_out"
FLASH_LSE_NAME = "flash_attention_lse"


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, window, q_shared=None,
                    k_shared=None):
    _check_window(q, k, causal, window)
    out, lse = _flash_forward(
        q, k, v, q_shared, k_shared, causal=causal, block_q=block_q,
        block_k=block_k, interpret=_interpret(), return_lse=True,
        window=window,
    )
    # ONE named ``out`` is both the value returned (what the caller's
    # output projection reads) and the backward kernels' residual: were
    # either a tensor the policy does not save, the recompute would run
    # the kernel for it. ``lse`` is named as [bh, tq]: saved as the kernel
    # writes it, [bh, tq, 1] float32, its last dimension can be padded to
    # the 128 lanes in HBM.
    out = checkpoint_name(out, FLASH_OUT_NAME)
    with jax.named_scope("attn_layout"):
        lse = lse[..., 0]
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return out, (q, k, v, out, lse, q_shared, k_shared)


def _flash_bwd_rule(causal, block_q, block_k, window, res, g):
    q, k, v, out, lse, q_shared, k_shared = res
    with jax.named_scope("attn_layout"):
        lse = lse[..., None]        # back to the kernels' column
    return _flash_backward(
        q, k, v, out, lse, g, q_shared, k_shared, causal=causal,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
        window=window,
    )


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def attention(q, k, v, *, causal: bool = True, impl: str = "auto",
              window: int | None = None, q_shared=None, k_shared=None):
    """Dispatch: 'reference' | 'blockwise' | 'flash' | 'auto'.

    ``window`` (causal self-attention only): a query sees the last
    ``window`` keys, its own among them; every path takes it, and a
    window that holds the whole row is the plain causal path (None).

    'auto' at Tk <= 1024 materialises the scores: causal self-attention
    whose T is a whole number, at least two, of query blocks (a quarter
    of T, in whole 128-row tiles) takes ``causal_blocked_attention``,
    anything else the plain reference.
    Above 1024 it uses the Pallas kernel on TPU where both lengths are
    whole 128-row tiles (the kernel sizes its own tiles from the shapes:
    ``_flash_tiles``), else the blockwise path.

    ``q_shared`` / ``k_shared``: a part of the key all heads share
    (``flash_attention``). The kernel takes the parts as they are; every
    other path gets them joined to q and to k repeated to the heads.
    """
    _check_window(q, k, causal, window)
    if window is not None and window >= k.shape[1]:
        window = None
    tq, tk = q.shape[1], k.shape[1]
    dr = 0 if q_shared is None else q_shared.shape[-1]
    if impl == "flash" or (
            impl == "auto" and tk > 1024
            and jax.devices()[0].platform == "tpu"
            and _flash_tiles("fwd", tq, tk, q.shape[-1], q.dtype,
                             v.shape[-1], dr)):
        return flash_attention(q, k, v, causal, None, None, window,
                               q_shared, k_shared)
    if q_shared is not None:
        q = jnp.concatenate([q, q_shared], axis=-1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_shared[:, :, None], (*k.shape[:3], dr))], axis=-1)
    if impl == "reference":
        return dot_product_attention(q, k, v, causal=causal, window=window)
    if impl == "blockwise" or tk > 1024:
        return blockwise_attention(q, k, v, causal=causal, window=window)
    # Up to 1024 keys the scores are materialised by XLA, and for causal
    # self-attention only the blocks at or under the diagonal (PERF.md
    # section 6, PR 25: the v5e runs of both benchmark cells that settled
    # this branch). The Pallas kernel starts above 1024 because at 1024,
    # with the one 1024-row tile its rule gives it, it loses to these
    # blocks at GPT-2 XL's shape and only draws level at Mistral's:
    # forward + the one-kernel backward 4.26-4.32 against 3.24 ms at
    # [8, 1024, 25, 64] and 3.20-3.26 against 3.43 at [4, 1024, 32, 128]
    # (v5e, a jitted gradient with the layout moves in it, PERF.md
    # section 7, PR 38; with two backward kernels 4.93 and 3.55 there,
    # PR 28's 3.97 / 2.33 and 2.66 / 2.50 in its own harness). The
    # threshold is ROADMAP A1's to move.
    rows = _causal_block_rows(tq) if causal and tq == tk else 0
    if rows:
        return causal_blocked_attention(q, k, v, block_q=rows, window=window)
    return dot_product_attention(q, k, v, causal=causal, window=window)
