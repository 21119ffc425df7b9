"""Mixture-of-Experts ops: top-k routing with static shapes, two dispatches.

The reference has no expert parallelism anywhere (SURVEY.md §2.4: EP —
"Absent"; vLLM handles MoE internally for inference only), so this is
greenfield. One router (float32 scores over the experts, a softmax or a
sigmoid each; top-k, under a bias that only the choice sees where the
model has one; the gates renormalised or not, and scaled) feeds one of
two dispatches, chosen by the model's ``expert_capacity_factor``:

* **dropless** (``None``; ``moe_swiglu_dropless``): the ``top_k x tokens``
  (token, choice) assignments are SORTED by expert, the tokens' rows
  gathered into that order, and each expert multiplies its own ragged
  run of rows (a grouped matmul: the bundled megablox Pallas kernel on a
  TPU, ``jax.lax.ragged_dot`` anywhere else). The results are gathered
  back into token order and summed under their gates. Every assignment
  is computed, whatever the imbalance; every shape is static (the rows
  are ``top_k x tokens`` in all, only the group boundaries are data);
  the arithmetic is the experts' own and nothing more. What OLMoE (64
  experts, top-8) trains with. It can be told which experts it HOLDS
  (``held``: one expert-parallel rank's consecutive share, what
  SmallThinker's cell runs: 16 of 64): the router, the top-k and the
  gates stay over all the experts, the assignments to held experts sort
  to the front, the rest behind them as one run with no matrix, which
  no grouped matmul visits (megablox's own sharded-groups case: more
  group sizes than matrices, the rows past the last matrix zeroed), and
  the result is the held experts' part of the sum. The row buffer
  FOLLOWS the share held: ``C`` = twice the rows a balanced router sends
  this rank, in whole row tiles (``_buffer_rows``; half of the ``top_k x
  tokens`` = A rows for a quarter of the experts, all A from a half up).
  The step COUNTS the batch's assignments to the held experts before it
  moves a row and runs the sorted rows through the experts ``C`` at a
  time (``_held_block``: one loop in the forward, one in the block's
  own backward rule, as many rounds as hold every held assignment: ONE
  unless the router overflows ``C``), so no assignment to a held expert
  is ever dropped and nothing is approximated; ``full_buffer`` in the
  statistics says whether a layer needed more than the one round.
  The caller may make the router's logits itself (``router_logits``;
  ``router_matmul``), for a model whose router does not read the
  experts' input, and the activation is SwiGLU's or ReGLU's; experts
  WITHOUT a gate projection (``w_gate`` None: ``W_down act(W_up u)``,
  Nemotron-H's under "relu2") run two grouped matmuls where the gated
  ones run three, on either path (``_gated``), and an inner width that
  is no whole number of 128-lane tiles is padded with zeros for the
  TPU's kernel (``_lane_whole``), on either path too. A model
  with a shared expert adds ``shared_expert``, which every token passes
  through whole, to the block's output beside this. What is
  NOT here is the exchange: there is no all-to-all yet (ROADMAP B2),
  so a rank's share runs alone and nothing stands in for the others.
* **capacity** (a number; ``moe_swiglu``, GShard / Switch style): dense
  one-hot dispatch / combine einsums over ``capacity`` slots an expert
  and group; what overflows is DROPPED (it passes through the residual).
  Under a mesh the expert dimension of the dispatched activations is
  sharded over the ``expert`` axis (parallel.mesh.AXIS_EXPERT) and GSPMD
  lowers the einsums into ``all_to_all`` collectives over ICI. Its
  dispatch arithmetic grows with ``experts x capacity`` a token (at 64
  experts nearly as much again as the experts' own matmuls, ROADMAP A8),
  and its loop over choices is unrolled: it is for few experts and small
  top-k (``tiny_moe``, ``moe_small``, ``mixtral_8x7b``).

Router losses. Balance (Switch Transformer): ``E * sum_e f_e * P_e``,
``P_e`` the mean router probability of expert e. The capacity path takes
``f_e`` as the share of a GROUP's tokens that hold a slot at e (sums to
top_k when nothing is dropped), mean over groups; the dropless path as
the share of the whole batch's assignments that went to e (sums to 1; 1.0
at uniform routing). z (ST-MoE): the mean over tokens of
``logsumexp(router logits)^2``. ``load_max`` is a counter, not a loss:
the fullest expert's assignments over the mean (1.0 is balance).

The dropless path's parts run under the named scopes of ``SCOPES``
(inside the model's ``moe``), so a profile says what routing costs
beyond the experts' arithmetic.

Which rows the dropless path moves, and when (N tokens, A = ``top_k`` x N
assignments, D the model's width; a test reads this off the gradient's
jaxpr). A gather INTO expert order reads an [N, D] source at ``order //
top_k``; a gather by ``inverse`` reads an [A, D] source, ``top_k`` times
the bytes, which no fast memory holds (on the v5e at OLMoE's sizes: 2.2 ms
from HBM against 0.4 where XLA keeps the 33-MB source on the chip;
PERF.md section 5). The block does two of the second kind and no more:

* forward: ``x[order // top_k]`` (dispatch), then ``y[inverse]``
  (combine, from [A, D]);
* recompute (under ``remat``): the dispatch's gather and the gate and up
  matmuls, for ``h``. NOT the down matmul and NOT ``y[inverse]``: the
  block's end has its own gradient (``_down_and_combine``) that reads
  ``h`` and never ``y``. The gates' gradient is taken against ``h``:
  ``d_gate[a] = <y[a], d_out[token(a)]> = <h[a], dh_u[a]>``, ``dh_u``
  the down matmul's row gradient before the gate, which the backward
  computes anyway;
* backward: ``d_out[order // top_k]`` (combine, from [N, D]: the
  cotangent of row a of ``y`` is ``gate[a]`` times it), then
  ``g[inverse]`` (dispatch, from [A, D], summed over a token's choices).
  The gates go to expert order and their gradient back as sorts of A
  scalars.

With a row buffer of C < A rows (a rank that holds under half of the
experts) a round moves the rows ``[i x C, (i + 1) x C)`` of the expert
order: the three gathers at ``order // top_k`` write [C, D], and the
grouped matmuls and everything elementwise between them run over C rows.
The two moves by ``inverse`` read a [C, D] source a CHOICE at a time,
``top_k`` gathers of [N, D] each summed in float32 as they come (no
[A, D] rows and no float32 [N, top_k, D] copy of them between the
rounds): an assignment outside the round reads a clamped index, under a
gate that is 0 in the forward and a mask in the backward. There the
recompute of the rows, gate and up is the backward RULE's own (what
crosses from the forward is the block's inputs), with or without remat.
A second round (the router overflowed ``C``) reads every token's
choices again.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

# Sub-scopes of the model's ``moe`` scope (models/transformer.py), opened
# by ``moe_swiglu_dropless``: router matmul, scores, top-k and the loss
# terms; the sort and the gather into expert order; the three grouped
# matmuls and the activation; the gather back, the gates and the sum. And
# by ``shared_expert`` / ``gated_shared_expert``: the gated FFN every token
# passes through beside its routed experts (and the one number a token
# that may scale it).
SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
          "moe_shared")
ROUTER_SCORES = ("softmax", "sigmoid")


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Per-expert token slots, rounded up to a multiple of 8 (lane-friendly)."""
    c = int(math.ceil(top_k * num_tokens / num_experts * capacity_factor))
    return max(8, ((c + 7) // 8) * 8)


def route(router_logits, top_k: int, norm_topk: bool = True, *,
          score: str = "softmax", select_bias=None, gate_scale: float = 1.0):
    """Router logits [G, E] -> (probs [G, E] float32, gates [G, k], chosen
    experts [G, k]). ``norm_topk`` renormalises the selected gates so a
    token's combine weights sum to 1 (Mixtral); OLMoE uses them as they
    are.

    ``score="sigmoid"`` scores each expert by itself (DeepSeek-V3's
    router); ``probs`` are then the scores over their sum, what the
    balance statistic reads. ``select_bias`` [E] is added to the scores
    FOR THE CHOICE ALONE: the top-k is of ``scores + bias``, the gates are
    the chosen experts' scores without it, and no gradient reaches it
    (its place is an integer index). ``gate_scale`` multiplies the gates
    after the renormalisation."""
    logits = router_logits.astype(jnp.float32)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
    if select_bias is None:
        topv, topi = jax.lax.top_k(scores, top_k)
    else:
        _, topi = jax.lax.top_k(jax.lax.stop_gradient(
            scores + select_bias.astype(jnp.float32)), top_k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    if norm_topk:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    if gate_scale != 1.0:
        topv = topv * gate_scale
    return probs, topv, topi


def bias_swapped(router_logits, chosen, top_k: int):
    """The share of the assignments ``chosen`` [G, k] that the top-k of
    the logits alone would not have made: what a selection bias changed.
    A chosen expert is in that top-k iff fewer than k experts score
    higher (scores are monotone in the logits, so the logits serve)."""
    logits = router_logits.astype(jnp.float32)
    own = jnp.take_along_axis(logits, chosen, axis=-1)            # [G, k]
    higher = (logits[:, None, :] > own[:, :, None]).sum(-1)       # [G, k]
    return jnp.mean((higher >= top_k).astype(jnp.float32))


def router_z(router_logits):
    """Mean over tokens of logsumexp(logits)^2, in float32."""
    lse = jax.scipy.special.logsumexp(
        router_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(jnp.square(lse))


def _assignment_counts(topi, num_experts: int):
    """Assignments an expert, over every leading dimension: int32 [E]."""
    return jax.nn.one_hot(topi.reshape(-1), num_experts,
                          dtype=jnp.int32).sum(0)


def _load_max(counts):
    return counts.max() / jnp.maximum(counts.mean(dtype=jnp.float32), 1e-9)


def topk_dispatch(router_logits, top_k: int, capacity: int,
                  norm_topk: bool = True):
    """Build dispatch/combine tensors from router logits [G, E].

    Returns (dispatch [G, E, C] float, combine [G, E, C] float, aux_loss
    scalar). Tokens are assigned to their top-k experts in choice order;
    each expert has C slots filled first-come-first-served (position =
    running count of earlier tokens choosing it); overflow tokens are
    dropped for that expert (their combine weight is 0 → they pass
    through the residual unchanged, the standard Switch behavior).
    """
    G, E = router_logits.shape
    probs, topv, topi = route(router_logits, top_k, norm_topk)

    counts = jnp.zeros((E,), jnp.int32)
    dispatch = jnp.zeros((G, E, capacity), jnp.float32)
    combine = jnp.zeros((G, E, capacity), jnp.float32)
    for j in range(top_k):  # unrolled: this path is for small top_k
        oh = jax.nn.one_hot(topi[:, j], E, dtype=jnp.int32)  # [G, E]
        pos = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]  # slot per token
        counts = counts + oh.sum(axis=0)
        # Slot index at the chosen expert; capacity overflow → index C,
        # which one_hot maps to an all-zero row (the token is dropped).
        pos_sel = (pos * oh).sum(-1)  # [G]
        kept = ((pos < capacity) & (oh > 0)).any(-1)
        slot = jax.nn.one_hot(jnp.where(kept, pos_sel, capacity),
                              capacity, dtype=jnp.float32)  # [G, C]
        d_j = oh.astype(jnp.float32)[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d_j
        combine = combine + topv[:, j][:, None, None] * d_j

    # Switch aux loss on the FULL probability mass (pre-top-k).
    frac_routed = dispatch.sum(axis=(0, 2)) / jnp.maximum(G, 1)  # f_e
    mean_prob = probs.mean(axis=0)  # p_e
    aux = E * jnp.sum(frac_routed * mean_prob)
    return dispatch, combine, aux


def _group_size(total: int, target: int) -> int:
    """Largest divisor of ``total`` that is <= target (trace-time)."""
    g = min(target, total)
    while total % g:
        g -= 1
    return g


def moe_swiglu(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               capacity_factor: float = 1.25, group_size: int = 1024,
               norm_topk: bool = True, constrain_fn=None):
    """MoE SwiGLU FFN for one layer, capacity dispatch (tokens over an
    expert's capacity are dropped).

    x [B, S, D]; router_w [D, E]; w_gate/w_up [E, D, F]; w_down [E, F, D].
    Returns (out [B, S, D], {"balance", "z", "load_max"} scalars).

    Tokens are processed in GROUPS of ~``group_size`` (GShard-style):
    dispatch/combine are [n, g, E, C_g] with C_g ∝ g, so memory and
    dispatch FLOPs scale O(G·g) instead of the O(G²) a single global
    dispatch would cost — the difference between fitting seq-2048
    batches in HBM and not. ``constrain_fn`` (optional) annotates the
    [n, E, C, D] dispatched activations (group dim batch-sharded, expert
    dim over the expert axis) so GSPMD inserts the all_to_alls.
    """
    B, S, D = x.shape
    E = router_w.shape[-1]
    G = B * S
    dt = x.dtype
    g = _group_size(G, group_size)
    n = G // g
    xg = x.reshape(n, g, D)
    logits = jnp.einsum("ngd,de->nge", xg.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    C = expert_capacity(g, E, top_k, capacity_factor)
    dispatch, combine, aux = jax.vmap(
        lambda lg: topk_dispatch(lg, top_k, C, norm_topk)
    )(logits)  # [n, g, E, C] ×2, aux [n]
    ein = xg.astype(jnp.float32)
    expert_in = jnp.einsum("ngec,ngd->necd", dispatch, ein).astype(dt)
    if constrain_fn is not None:
        expert_in = constrain_fn(expert_in)
    gate = jax.nn.silu(jnp.einsum("necd,edf->necf", expert_in,
                                  w_gate.astype(dt)))
    up = jnp.einsum("necd,edf->necf", expert_in, w_up.astype(dt))
    expert_out = jnp.einsum("necf,efd->necd", gate * up, w_down.astype(dt))
    if constrain_fn is not None:
        expert_out = constrain_fn(expert_out)
    out = jnp.einsum("ngec,necd->ngd", combine,
                     expert_out.astype(jnp.float32)).astype(dt)
    # what was ASKED of each expert, before any drop
    counts = _assignment_counts(jax.lax.top_k(logits, top_k)[1], E)
    stats = {"balance": aux.mean(), "z": router_z(logits),
             "load_max": _load_max(counts)}
    return out.reshape(B, S, D), stats


# -- dropless ----------------------------------------------------------------

# megablox tiles (rows, contraction, columns) of the three kernels a
# grouped matmul's forward and backward are made of, by the contraction's
# width; settled by a sweep on the v5e at OLMoE's shapes, 65,536 rows x 64
# groups, 2048 <-> 1024 (PERF.md section 6, PR 27). Larger tiles do not
# fit the kernels' 16 MB of VMEM.
_GMM_ROWS = 512                      # every kernel's row tile divides this


def _gmm_tiles(k: int) -> tuple[int, int, int]:
    """Tiles of ``gmm`` (forward, and the gradient of the rows) for a
    contraction ``k`` wide."""
    return (512 if k > 1024 else 256, 1024, 1024)


_TGMM_TILES = (256, 1024, 1024)      # the gradient of the weights


def _use_megablox(lhs, rhs) -> bool:
    """The Pallas grouped-matmul kernel applies on a TPU, to bfloat16
    rows that divide into its row tile and widths in whole lane tiles."""
    (m, k), n = lhs.shape, rhs.shape[-1]
    return (jax.devices()[0].platform == "tpu"
            and lhs.dtype == rhs.dtype == jnp.bfloat16
            and m % _GMM_ROWS == 0 and k % 128 == 0 and n % 128 == 0)


def _fit(tiles, k: int, n: int):
    """The tiles cut to the matrix: of each width its largest divisor in
    whole lane tiles up to the tile (1024 for 2048 and 1024 themselves,
    640 for SmallThinker's 2560, 768 for its experts' 768)."""
    def most(width, tile):
        return max(t for t in range(128, min(tile, width) + 1, 128)
                   if width % t == 0)

    return (tiles[0], most(k, tiles[1]), most(n, tiles[2]))


def _megablox():
    # the package's ``gmm`` attribute is its custom_vjp wrapper, which
    # shadows the module of the kernels themselves
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@jax.custom_vjp
def _megablox_matmul(lhs, rhs, group_sizes):
    k, n = rhs.shape[1], rhs.shape[2]
    return _megablox().gmm(lhs, rhs, group_sizes, lhs.dtype,
                           _fit(_gmm_tiles(k), k, n))


def _megablox_fwd(lhs, rhs, group_sizes):
    return _megablox_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_grads(lhs, rhs, group_sizes, g):
    """Both gradients of ``grouped_matmul(lhs, rhs, group_sizes)`` under
    the cotangent ``g`` [M, N]: of the rows, ``g`` times each group's
    matrix transposed [M, K] (zero in the rows of a group with no
    matrix), and of the weights, each group's own ``lhs^T x g``
    [G, K, N]. Neither reads the product itself."""
    k, n = rhs.shape[1], rhs.shape[2]
    if _use_megablox(lhs, rhs):
        backend = _megablox()
        dlhs = backend.gmm(g, rhs, group_sizes, lhs.dtype,
                           _fit(_gmm_tiles(n), n, k), transpose_rhs=True)
        drhs = backend.tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                            _fit(_TGMM_TILES, k, n),
                            num_actual_groups=rhs.shape[0])
        return dlhs, drhs
    group_sizes = group_sizes[:rhs.shape[0]]
    dlhs = jax.lax.ragged_dot(g, rhs.swapaxes(1, 2), group_sizes)
    drhs = jax.lax.ragged_dot_general(
        lhs, g, group_sizes, jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]))
    return dlhs, drhs


def _megablox_bwd(res, g):
    lhs, rhs, group_sizes = res
    return (*_grouped_matmul_grads(lhs, rhs, group_sizes, g),
            np.zeros(group_sizes.shape, jax.dtypes.float0))


_megablox_matmul.defvjp(_megablox_fwd, _megablox_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` [M, K] rows sorted by group, ``rhs`` [G, K, N],
    ``group_sizes`` int32 [G] summing to M -> [M, N]: each run of rows
    times its own group's matrix. ``group_sizes`` may count MORE groups
    than ``rhs`` holds matrices for (the runs of rows behind the held
    experts'): no kernel visits those rows and the product is zero
    there. The selection is by what can be seen (platform, dtype,
    shapes), like ``attention(impl="auto")``."""
    if _use_megablox(lhs, rhs):
        return _megablox_matmul(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes[:rhs.shape[0]])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_experts(x, order, inverse, top_k: int):
    """x [N, D] -> [N * top_k, D]: row a is the token of assignment
    ``order[a]`` (assignment n * top_k + j is token n's j-th choice). A
    gather whose transpose is written as a gather too: TPU scatters are
    slow, and this one is a permutation of ``top_k`` copies."""
    return x[order // top_k]


def _rows_to_experts_fwd(x, order, inverse, top_k):
    return x[order // top_k], (inverse, x.shape[0])


def _rows_to_experts_bwd(top_k, res, g):
    inverse, n = res
    dx = g[inverse].reshape(n, top_k, g.shape[-1]).astype(jnp.float32).sum(1)
    return dx.astype(g.dtype), None, None


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


@jax.custom_vjp
def _down_and_combine(h, w_down, gates, counts, order, inverse):
    """The block's end as one function with its own gradient: ``h``
    [A, F] (``silu(gate) * up``, expert order) times each expert's
    ``w_down`` [E, F, D], the rows gathered back to token order and
    summed under ``gates`` [N, top_k] in float32 -> [N, D].

    The gradient is taken in EXPERT order and reads ``h``, never the
    product ``y``: the cotangent of row a of ``y`` is ``gate[a] *
    d_out[order[a] // top_k]``, and ``d_gate[a] = <y[a], d_out[token]> =
    <h[a], dh_u[a]>`` with ``dh_u = d_out[token] x w_down^T``, the row
    gradient of the down matmul before the gate. So the backward gathers
    from ``d_out`` [N, D] (as the dispatch's forward does from ``x``),
    gathers from no [A, D] array, and under remat neither the down
    matmul nor the gather back is recomputed."""
    n, top_k = gates.shape
    with jax.named_scope("moe_experts"):
        y = grouped_matmul(h, w_down, counts)
    with jax.named_scope("moe_combine"):
        rows = y[inverse].reshape(n, top_k, y.shape[-1])
        out = (rows.astype(jnp.float32) * gates[:, :, None]).sum(axis=1)
        return out.astype(y.dtype)


def _down_and_combine_fwd(h, w_down, gates, counts, order, inverse):
    return (_down_and_combine(h, w_down, gates, counts, order, inverse),
            (h, w_down, gates, counts, order, inverse))


def _permuted(values, to):
    """``values`` [A] with element i moved to place ``to[i]`` (``to`` a
    permutation). A sort by ``to``: the TPU sorts 65,536 pairs in a tenth
    of the time it gathers as many scalars."""
    return jax.lax.sort((to, values), num_keys=1)[1]


def _down_grads(h, w_down, counts, gate, g):
    """What the down matmul's backward makes of ``h`` [R, F], the rows'
    gates [R, 1] and their share ``g`` [R, D] of the block's cotangent:
    the gradients of ``h``, of ``w_down`` and of the gates [R]."""
    with jax.named_scope("moe_experts"):
        hf = h.astype(jnp.float32)
        dh_u, dw = _grouped_matmul_grads((gate * hf).astype(h.dtype), w_down,
                                         counts, g)
        dh_u = dh_u.astype(jnp.float32)
        d_gate = (hf * dh_u).sum(axis=-1)
        return (gate * dh_u).astype(h.dtype), dw, d_gate


def _down_and_combine_bwd(res, d_out):
    h, w_down, gates, counts, order, inverse = res
    top_k = gates.shape[1]
    with jax.named_scope("moe_combine"):
        gate = _permuted(gates.reshape(-1), inverse)[:, None]   # [A, 1]
        g = d_out[order // top_k]                               # [A, D]
    dh, dw, d_gate = _down_grads(h, w_down, counts, gate, g)
    with jax.named_scope("moe_combine"):
        d_gates = _permuted(d_gate, order).reshape(gates.shape)
    return dh, dw, d_gates, None, None, None


_down_and_combine.defvjp(_down_and_combine_fwd, _down_and_combine_bwd)


ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def router_matmul(x, router_w):
    """The router's float32 logits [..., E] of ``x`` [..., D], under the
    scope ``moe_router``: for a model whose router reads something other
    than the experts' input (SmallThinker: the block's FIRST norm), so
    the caller makes them where it has that input."""
    with jax.named_scope("moe_router"):
        return jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                          router_w.astype(jnp.float32))


def shared_expert(x, w_gate, w_up, w_down, activation: str = "silu"):
    """The gated FFN every token passes through beside its routed experts
    (DeepSeek's shared experts, fused into one SwiGLU), ungated, under
    the scope ``moe_shared``. x [..., D]; w_gate / w_up [D, F]; w_down
    [F, D]. ``w_gate`` None: an expert WITHOUT a gate projection,
    ``activation(x W_up) W_down`` (Nemotron-H's, under "relu2")."""
    with jax.named_scope("moe_shared"):
        if w_gate is None:
            return ACTIVATIONS[activation](x @ w_up) @ w_down
        return (ACTIVATIONS[activation](x @ w_gate) * (x @ w_up)) @ w_down


def gated_shared_expert(x, w_gate, w_up, w_down, w_share):
    """``shared_expert`` times ``sigmoid(w_share . x)``, ONE number a
    token (``w_share`` [D]; the sigmoid and the product float32), all of
    it under ``moe_shared`` -> (the gated output, the gate's mean: 0.5 at
    a seeded init, 0 where the gate has shut and the shared expert is paid
    for by nobody; no gradient)."""
    with jax.named_scope("moe_shared"):
        share = jax.nn.sigmoid(jnp.einsum(
            "...d,d->...", x, w_share.astype(x.dtype),
            preferred_element_type=jnp.float32))[..., None]
        out = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return ((out * share).astype(out.dtype),
                jax.lax.stop_gradient(share).mean())


def held_range(num_experts: int, rank: int, of: int) -> tuple[int, int]:
    """[first, end) of the experts that rank ``rank`` of ``of`` holds:
    ``num_experts / of`` consecutive ones."""
    if num_experts % of or not 0 <= rank < of:
        raise ValueError(f"{num_experts} experts do not divide over {of} "
                         f"ranks, or rank {rank} is not one of them")
    share = num_experts // of
    return rank * share, (rank + 1) * share


# A rank that holds Eh of E experts keeps a row buffer of this many times
# the rows a balanced router sends it. The seeded routers on record read
# 0.10-0.44 of the assignments held where 0.25 is balance and 0-0.13 where
# 0.125 is (PERF.md section 6, PR 31 / 34 / 35): twice covers them in all
# but a layer now and then, which takes a second round and says so.
_HELD_ROOM = 2


def _buffer_rows(assignments: int, held: int, of: int) -> int:
    """Rows of the expert-order buffer of a rank that holds ``held`` of
    ``of`` experts: ``_HELD_ROOM`` times its balanced share of the
    ``assignments``, in whole row tiles; all of them from a share of 1 /
    ``_HELD_ROOM`` up."""
    rows = -(-_HELD_ROOM * assignments * held // of)
    return min(assignments, -(-rows // _GMM_ROWS) * _GMM_ROWS)


def _lane_whole(w_gate, w_up, w_down):
    """The experts' matrices with their inner width ``F`` padded by zero
    columns (rows of ``w_down``) to whole 128-lane tiles, where a TPU's
    grouped-matmul kernel would otherwise step aside for an ``F`` that is
    none (Nemotron-H's 1,856 = 14.5 tiles; ``_use_megablox``). The padded
    columns make ``activation(0)`` x 0 = 0 against zero rows of
    ``w_down``: nothing of the result moves, the leaves and their
    gradients keep the published width (a pad's transpose is a slice).
    Anywhere else, and at a width in whole tiles, the matrices as they
    are."""
    pad = -w_up.shape[-1] % 128
    if not pad or jax.devices()[0].platform != "tpu":
        return w_gate, w_up, w_down
    wider = ((0, 0), (0, 0), (0, pad))
    return (None if w_gate is None else jnp.pad(w_gate, wider),
            jnp.pad(w_up, wider), jnp.pad(w_down, ((0, 0), (0, pad), (0, 0))))


def _gated(rows, w_gate, w_up, counts, activation: str):
    """``activation(gate) * up`` of the rows, each by its own expert;
    ``activation(up)`` for experts without a gate (``w_gate`` None: two
    grouped matmuls an expert layer, not three)."""
    with jax.named_scope("moe_experts"):
        if w_gate is None:
            return ACTIVATIONS[activation](grouped_matmul(rows, w_up, counts))
        return ACTIVATIONS[activation](grouped_matmul(
            rows, w_gate, counts)) * grouped_matmul(rows, w_up, counts)


def _round(i, buffer: int, held_counts, order, inverse, top_k: int):
    """Round ``i`` of a rank's held rows: the rows ``[i x buffer, (i + 1)
    x buffer)`` of the expert order. Returns the tokens of those rows
    [buffer] (``order`` comes padded to whole rounds), their group sizes
    (what of each held expert's run lies in the round and, behind, one
    run with no matrix), and for every assignment [N, top_k] its row in
    the round, clamped, and whether it is in the round at all."""
    start = i * buffer
    ends = jnp.cumsum(held_counts)
    sizes = (jnp.clip(ends - start, 0, buffer)
             - jnp.clip(ends - held_counts - start, 0, buffer))
    counts = jnp.concatenate([sizes, (buffer - sizes.sum())[None]])
    tokens = jax.lax.dynamic_slice(order, (start,), (buffer,)) // top_k
    row = (inverse - start).reshape(-1, top_k)
    return (tokens, counts, jnp.clip(row, 0, buffer - 1),
            (row >= 0) & (row < buffer))


def _rounds(held_counts, buffer: int):
    """Rounds of ``buffer`` rows that hold every assignment to a held
    expert; one where there is none (the step costs what it costs)."""
    return jnp.maximum(1, -(-held_counts.sum() // buffer))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _held_block(x, w_gate, w_up, w_down, gates, held_counts, order, inverse,
                buffer: int, activation: str):
    """The sorted assignments' way through the experts a rank holds,
    ``buffer`` < A rows of the expert order at a time: x [N, D] -> the
    sum under ``gates`` [N, top_k] (0 for an absent expert) of each
    token's rows [N, D], in float32 over the rounds. The batch's held
    assignments are counted before a row moves; they fit ONE round unless
    the router overflows the buffer, and then there are as many rounds as
    hold them all: the same rows meet the same matrices in the same
    tiles, and none is dropped. One loop with one body, so the step holds
    the block's code once, over ``buffer`` rows (a ``cond`` between this
    and the A-row block held it twice and loaded 15% slower, PERF.md
    section 6, PR 36); with its own gradient, since a loop of a counted
    length has none, and so that what crosses from the forward to the
    backward is the block's inputs."""
    top_k = gates.shape[1]
    order = jnp.pad(order, (0, -order.shape[0] % buffer))

    def one_round(i, out):
        tokens, counts, row, here = _round(i, buffer, held_counts, order,
                                           inverse, top_k)
        with jax.named_scope("moe_dispatch"):
            rows = x[tokens]
        h = _gated(rows, w_gate, w_up, counts, activation)
        with jax.named_scope("moe_experts"):
            y = grouped_matmul(h, w_down, counts)
        with jax.named_scope("moe_combine"):
            # a choice at a time: no [A, D] rows between the loop's rounds
            gate = jnp.where(here, gates, 0.0)
            for j in range(top_k):
                out = out + (y[row[:, j]].astype(jnp.float32)
                             * gate[:, j, None])
            return out

    with jax.named_scope("moe_combine"):
        out = jnp.zeros(x.shape, jnp.float32)
    out = jax.lax.fori_loop(0, _rounds(held_counts, buffer), one_round, out)
    with jax.named_scope("moe_combine"):
        return out.astype(x.dtype)


def _held_block_fwd(x, w_gate, w_up, w_down, gates, held_counts, order,
                    inverse, buffer, activation):
    inputs = (x, w_gate, w_up, w_down, gates, held_counts, order, inverse)
    return _held_block(*inputs, buffer, activation), inputs


def _held_block_bwd(buffer, activation, inputs, d_out):
    """As ``_down_and_combine``'s and ``_rows_to_experts``' gradients, a
    round at a time: the rows and ``h`` are made again here (what a
    layer's remat does anyway), never ``y``."""
    x, w_gate, w_up, w_down, gates, held_counts, order, inverse = inputs
    top_k = gates.shape[1]
    a_rows, pad = order.shape[0], -order.shape[0] % buffer
    with jax.named_scope("moe_combine"):
        sorted_gates = jnp.pad(_permuted(gates.reshape(-1), inverse), (0, pad))
    padded = jnp.pad(order, (0, pad))

    def one_round(i, grads):
        dx, dw_gate, dw_up, dw_down, d_gate = grads
        tokens, counts, row, here = _round(i, buffer, held_counts, padded,
                                           inverse, top_k)
        with jax.named_scope("moe_dispatch"):
            rows = x[tokens]
        h, to_rows = jax.vjp(
            lambda rows, w_gate, w_up: _gated(rows, w_gate, w_up, counts,
                                              activation), rows, w_gate, w_up)
        with jax.named_scope("moe_combine"):
            gate = jax.lax.dynamic_slice(sorted_gates, (i * buffer,),
                                         (buffer,))[:, None]
            g = d_out[tokens]
        dh, dw, d_gate_here = _down_grads(h, w_down, counts, gate, g)
        d_rows, dw_g, dw_u = to_rows(dh)
        with jax.named_scope("moe_dispatch"):
            for j in range(top_k):
                dx = dx + jnp.where(here[:, j, None], d_rows[row[:, j]],
                                    0).astype(jnp.float32)
        with jax.named_scope("moe_combine"):
            d_gate = jax.lax.dynamic_update_slice(d_gate, d_gate_here,
                                                  (i * buffer,))
        with jax.named_scope("moe_experts"):
            return (dx, None if w_gate is None else dw_gate + dw_g,
                    dw_up + dw_u, dw_down + dw, d_gate)

    with jax.named_scope("moe_experts"):
        grads = (jnp.zeros(x.shape, jnp.float32),
                 None if w_gate is None else jnp.zeros_like(w_gate),
                 jnp.zeros_like(w_up), jnp.zeros_like(w_down),
                 jnp.zeros((a_rows + pad,), jnp.float32))
    dx, dw_gate, dw_up, dw_down, d_gate = jax.lax.fori_loop(
        0, _rounds(held_counts, buffer), one_round, grads)
    with jax.named_scope("moe_combine"):
        d_gates = _permuted(d_gate[:a_rows], order).reshape(gates.shape)
    with jax.named_scope("moe_dispatch"):
        dx = dx.astype(x.dtype)
    return dx, dw_gate, dw_up, dw_down, d_gates, None, None, None


_held_block.defvjp(_held_block_fwd, _held_block_bwd)


def moe_swiglu_dropless(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                        norm_topk: bool = True, router_logits=None,
                        held: tuple[int, int] | None = None,
                        activation: str = "silu", score: str = "softmax",
                        select_bias=None, gate_scale: float = 1.0):
    """MoE gated FFN (``activation(gate) * up``: SwiGLU with "silu",
    ReGLU with "relu") for one layer; every (token, choice) assignment
    to an expert that is here is computed.

    x [B, S, D]; router_w [D, E]; w_gate/w_up [Eh, D, F]; w_down
    [Eh, F, D]; ``w_gate`` None for experts with no gate projection
    (``activation(up)``: "relu2" is ``relu(.)^2``). ``router_logits`` [B,
    S, E], where the caller made them
    (``router_w`` is then not read). Returns (out [B, S, D], {"balance",
    "z", "load_max"} scalars); the losses are over all the tokens of
    ``x`` and all E experts (see the module docstring).

    ``held`` = [first, end): the weights are those Eh = end - first of
    the E experts, one expert-parallel rank's (``held_range``). The
    router, the top-k and the gates stay over all E; the assignments to
    the held experts sort to the front by expert, the others behind
    them as one run that no grouped matmul visits (its rows are zero);
    ``out`` is the held experts' part of the sum. The row buffer is
    twice the rank's balanced share of the ``top_k x tokens`` rows
    (``_buffer_rows``), and a layer whose held assignments overflow it
    takes as many rounds of it as hold them: whatever the imbalance, no
    assignment to a held expert is dropped. ``load_max`` is then over
    the held experts, and ``held_share`` (the share of the assignments
    that went to one; 1 / ranks at balance) and ``full_buffer`` (1.0
    where the held assignments overflowed the buffer and the layer took
    more than one round, else 0.0) join the statistics.

    ``score``, ``select_bias`` and ``gate_scale`` are ``route``'s. With a
    ``select_bias`` the statistics also hold ``counts`` (float32 [E]: the
    assignments of this batch to each of ALL E experts, what the bias's
    update rule reads) and ``bias_swapped`` (``bias_swapped``).
    """
    B, S, D = x.shape
    N, A = B * S, B * S * top_k
    dt = x.dtype
    xf = x.reshape(N, D)
    if router_logits is None:
        router_logits = router_matmul(xf, router_w)
    with jax.named_scope("moe_router"):
        logits = router_logits.reshape(N, -1).astype(jnp.float32)
        E = logits.shape[-1]
        probs, gates, experts = route(logits, top_k, norm_topk, score=score,
                                      select_bias=select_bias,
                                      gate_scale=gate_scale)
        counts = _assignment_counts(experts, E)
        stats = {"balance": E * jnp.sum(counts / A * probs.mean(axis=0)),
                 "z": router_z(logits)}
        if select_bias is not None:
            stats["counts"] = counts.astype(jnp.float32)
            stats["bias_swapped"] = bias_swapped(logits, experts, top_k)
        keys = experts.reshape(A).astype(jnp.int32)
        if held is None:
            stats["load_max"] = _load_max(counts)
        else:
            # Held experts first, by their place among the held; every
            # other assignment behind them, in one run with no matrix.
            first, end = held
            here = (experts >= first) & (experts < end)
            gates = jnp.where(here, gates, 0.0)
            keys = jnp.where(here.reshape(A), keys - first, end - first)
            held_counts = counts[first:end]
            counts = jnp.concatenate(
                [held_counts, (A - held_counts.sum())[None]])
            buffer = _buffer_rows(A, end - first, E)
            stats["load_max"] = _load_max(held_counts)
            stats["held_share"] = held_counts.sum() / jnp.float32(A)
            stats["full_buffer"] = (held_counts.sum() > buffer).astype(
                jnp.float32)
    with jax.named_scope("moe_dispatch"):
        # Stable sort of the assignments by expert: ``order[a]`` is the
        # assignment that lands in row a, ``inverse`` the other way.
        iota = jnp.arange(A, dtype=jnp.int32)
        _, order = jax.lax.sort((keys, iota), num_keys=1)
        inverse = jnp.zeros((A,), jnp.int32).at[order].set(iota)
    with jax.named_scope("moe_experts"):
        w_gate, w_up, w_down = _lane_whole(
            None if w_gate is None else w_gate.astype(dt),
            w_up.astype(dt), w_down.astype(dt))
    if held is not None and buffer < A:
        out = _held_block(xf, w_gate, w_up, w_down, gates, held_counts,
                          order, inverse, buffer, activation)
        return out.reshape(B, S, D), stats
    with jax.named_scope("moe_dispatch"):
        rows = _rows_to_experts(xf, order, inverse, top_k)
    h = _gated(rows, w_gate, w_up, counts, activation)
    out = _down_and_combine(h, w_down, gates, counts, order, inverse)
    return out.reshape(B, S, D), stats
