"""Shared plain pieces of the references: float32 attention in query
blocks (so long rows fit), log-softmax loss, what a reference's training
loss is, and THE comparison of a program with its reference, token by
token (``agreement`` and ``LIMITS``: the train driver's
``program_agrees_with_reference`` and ``python -m
chipbench.reference.compare`` both call it), with the controls that show
it failing (``CONTROLS``: one side's weights with one thing wrong). No
kernels, no cache; nothing here imports the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def causal_attention(q, k, v, *, block_q: int = 1024):
    """q, k, v [B, T, H, Dh] float32 (k, v already repeated to H heads)
    -> [B, T, H, Dh]. Softmax over the whole context, one block of query
    positions at a time so the [T, T] scores never exist at once."""
    B, T, H, Dh = q.shape
    scale = 1.0 / (Dh ** 0.5)
    outs = []
    for s in range(0, T, block_q):
        e = min(T, s + block_q)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:e], k[:, :e]) * scale
        mask = (jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :])
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :e]))
    return jnp.concatenate(outs, axis=1)


def layer_slice(layers, i: int):
    """Layer ``i`` of the program's stacked [n_layers, ...] parameters."""
    return jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)


def token_logprobs(logits, targets):
    """log p(targets) under float32 logits [B, T, V]; targets [B, T]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def next_token_loss(logits, tokens):
    """Mean next-token cross entropy of rows ``tokens`` [B, T + 1] given
    the logits of their first T positions."""
    return -token_logprobs(logits, tokens[:, 1:]).mean()


def training_loss(ref, params, tokens, cfg):
    """The whole training loss of the reference module ``ref`` on rows
    ``tokens`` [B, T + 1], or None where the module states none. A module
    whose recipe adds terms to the cross entropy (router losses, a z
    loss) exports ``loss(params, tokens, cfg)``, the whole float32 loss
    under ``default_matmul_precision("highest")``; for any other the
    whole loss IS the cross entropy of its ``forward``, which
    ``agreement`` reads from the logits it compares."""
    whole = getattr(ref, "loss", None)
    return None if whole is None else whole(params, jnp.asarray(tokens), cfg)


# What the program may differ by from its float32 reference, token by
# token, on the sample rows at the timed sizes and the published widths:
# ``agreement`` records four statistics and ``LIMITS`` holds two of them.
#   logit_rel_d    mean |z_p - z_r| over every position and vocabulary
#                  entry, over std(z_r): the absolute value BEFORE the
#                  mean, which a difference of mean losses averages away
#   rest_d         |(program's whole loss - cross entropy of z_p) -
#                  (reference's whole loss - cross entropy of z_r)|: the
#                  router terms alone (0 on both sides for a dense model,
#                  and for a reference that states no ``loss``)
#   argmax_agree   share of positions whose largest logit is the same entry
#   target_logp_d  mean over positions of |log p_p(target) - log p_r(target)|
# A limit is PLACED BY RULE from two readings on the chip, which stand
# beside it (``placed_by_rule``; ``tests/chipbench`` hold every limit
# there is to it): at least 2 x the sound bfloat16 program's worst reading
# over six seeds or more in every cell the limit serves, and no further
# than half way from that reading to the nearest control's.
# Readings on the v5e (PERF.md section 4 has the table by cell; section 6,
# PR 34, how they were taken), sound program / nearest control:
#   logit_rel_d  GPT-2 XL <= 0.0090 (16 seeds) / 0.0666 (float8 weights);
#     OLMoE <= 0.0143 (20) / 0.108 (float8 weights); SmallThinker <= 0.0084
#     (20) / 0.0413 (the last layer's held experts out); Mistral-7B at 10
#     layers 0.0391-0.0394 (12) / 0.258 (its last layer out). A reading is
#     steady from seed to seed (a mean over 10^8 entries: +-5%) and 6.9-9.0
#     x as large under float8 weights in every cell, but the cells differ
#     among themselves by 5 x: 0.03 obeys the rule for the first two (their
#     worst sound reading and their nearest control are the readings below),
#     and the other two configurations STATE THEIR OWN in their files
#     (``agreement_limits``: SmallThinker 0.02, Mistral 0.09) WITH the two
#     readings, which ``limits_for`` holds to the same rule or refuses.
#   rest_d  <= 2.3e-5 (61 sound readings, all cells) / 0.0116 (OLMoE's
#     balance term left out; SmallThinker's 0.0169): one limit for all,
#     which no file may restate (arithmetic, not rounding: it does not
#     move with width or depth).
#   argmax_agree, target_logp_d: RECORDED, NOT HELD. No limit obeys that
#     rule in every cell: SmallThinker's sound program reads argmax_agree
#     down to 0.957 where its weakest control reads 0.922 (2 x the sound
#     disagreement is 0.087, the control's 0.078); target_logp_d is
#     logit_rel_d x std(z_r) within 6% in every reading, and says nothing
#     logit_rel_d does not.
PLACED_BY = ("limit", "sound_worst", "nearest_control")
READINGS = {
    "logit_rel_d": {"limit": 0.03, "sound_worst": 0.0143,
                    "nearest_control": 0.0666},
    "rest_d": {"limit": 1e-3, "sound_worst": 2.3e-5,
               "nearest_control": 0.0116},
}
LIMITS = {name: ("<=", r["limit"]) for name, r in READINGS.items()}
# the statistics whose limit a configuration file may state for itself
STATED_BY_A_FILE = ("logit_rel_d",)
RECORDED = ("logit_rel_d", "argmax_agree", "target_logp_d", "rest_d")


@jax.jit
def _per_position(z_p, z_r, targets):
    """Reductions over the vocabulary only: [B, T] each, so the two
    [B, T, V] logit arrays are read and nothing of their size is made."""
    z_p, z_r = z_p.astype(jnp.float32), z_r.astype(jnp.float32)
    d = jnp.abs(z_p - z_r)
    return {"abs_d_sum": d.sum(-1), "abs_d_max": d.max(-1),
            "ref_sum": z_r.sum(-1), "ref_sq_sum": jnp.square(z_r).sum(-1),
            "same_top": z_p.argmax(-1) == z_r.argmax(-1),
            "logp_p": token_logprobs(z_p, targets),
            "logp_r": token_logprobs(z_r, targets)}


def agreement(ref, params, rows, cfg, program) -> dict:
    """The program against the plain reference ``ref`` on rows ``rows``
    [B, T + 1] (numpy), token by token. ``params`` is what the REFERENCE
    is given; ``program()`` is called once, after the reference has run,
    and returns (the program's float32 logits [B, T, V] of the rows'
    first T positions, its whole training loss on the rows). Both logit
    arrays are deleted before this returns, and the delete is waited for.
    Returns the four statistics (``RECORDED``) and what they were made
    of, as Python floats."""
    # The whole loss first: its pass frees its logits before the ones to
    # compare are kept, so the peak is ONE reference pass (kept across the
    # second pass they made it 11.8 GB, not 8.3, in the SmallThinker cell).
    ref_loss = training_loss(ref, params, rows, cfg)
    z_r = ref.forward(params, jnp.asarray(rows[:, :-1]), cfg)
    z_p, program_loss = program()
    per = jax.device_get(_per_position(z_p, z_r, jnp.asarray(rows[:, 1:])))
    vocab = z_r.shape[-1]
    for z in (z_p, z_r):
        z.delete()
    per = {k: np.asarray(v, np.float64) for k, v in per.items()}
    mean_r = per["ref_sum"].mean() / vocab
    std_r = float(np.sqrt(per["ref_sq_sum"].mean() / vocab - mean_r ** 2))
    ce_p, ce_r = -float(per["logp_p"].mean()), -float(per["logp_r"].mean())
    program_loss = float(program_loss)
    ref_loss = ce_r if ref_loss is None else float(ref_loss)
    return {
        "logit_rel_d": float(per["abs_d_sum"].mean() / vocab) / std_r,
        "argmax_agree": float(per["same_top"].mean()),
        "target_logp_d": float(np.abs(per["logp_p"] - per["logp_r"]).mean()),
        "rest_d": abs((program_loss - ce_p) - (ref_loss - ce_r)),
        "logit_std": std_r,
        "max_abs_d": float(per["abs_d_max"].max()),
        "token_max_median": float(np.median(per["abs_d_max"])),
        "positions": int(per["same_top"].size),
        "program_loss": program_loss, "program_ce": ce_p,
        "reference_loss": ref_loss, "reference_ce": ce_r,
    }


def placed_by_rule(limit: float, sound_worst: float,
                   nearest_control: float) -> bool:
    """The rule every limit of an upper kind is placed by: at least 2 x
    the sound program's worst reading, no further than half way from it
    to the nearest control's reading."""
    return 2 * sound_worst <= limit <= (sound_worst + nearest_control) / 2


def limits_for(stated: dict | None = None) -> dict:
    """``LIMITS``, with the limit a configuration file states for its own
    cells under ``agreement_limits`` in its place: ``{"logit_rel_d":
    {"limit": .., "sound_worst": .., "nearest_control": ..}, "why": ..}``,
    the two readings on the chip that place it (PERF.md section 4 has the
    table they come from). Refused: a statistic no file may state, a
    limit without its readings or its why, and a limit that its readings
    do not place by the rule (``placed_by_rule``)."""
    stated = dict(stated or {})
    why = stated.pop("why", None)
    unknown = set(stated) - set(STATED_BY_A_FILE)
    if unknown:
        raise ValueError(f"agreement_limits has {sorted(unknown)}; a file "
                         f"may state {list(STATED_BY_A_FILE)}")
    if stated and not (isinstance(why, str) and why.strip()):
        raise ValueError("agreement_limits states a limit and no why")
    limits = dict(LIMITS)
    for name, r in stated.items():
        try:
            nums = [float(r[k]) for k in PLACED_BY]
        except (TypeError, KeyError, ValueError) as e:
            raise ValueError(
                f"agreement_limits[{name!r}] has to give {list(PLACED_BY)} "
                f"as numbers: {e!r}") from None
        if not placed_by_rule(*nums):
            raise ValueError(
                f"agreement_limits[{name!r}]: limit {nums[0]} is not "
                f"between 2 x the sound program's worst reading "
                f"({2 * nums[1]}) and half way to the nearest control "
                f"({(nums[1] + nums[2]) / 2})")
        limits[name] = (LIMITS[name][0], nums[0])
    return limits


def outside(stats: dict, limits: dict = LIMITS) -> list[str]:
    """The statistics of ``limits`` that ``stats`` has outside their
    limit (a statistic that is missing or not a number is outside)."""
    bad = []
    for name, (op, limit) in limits.items():
        v = stats.get(name)
        ok = v is not None and (v <= limit if op == "<=" else v >= limit)
        if not ok:
            bad.append(name)
    return bad


def compared(stats: dict, limits: dict = LIMITS) -> list[str]:
    """One line a statistic: the number compared beside its limit."""
    bad = outside(stats, limits)
    return [f"compared {name} {stats.get(name)!r} (limit {op} {limit}): "
            f"{'OUTSIDE' if name in bad else 'ok'}"
            for name, (op, limit) in limits.items()]


# --- controls: one side's weights with one thing wrong ------------------
# Each is a transformation of the ``params`` ONE side is given, keyed on
# nothing but the leaf names ``init_params`` gives (``layers`` stacked
# [n_layers, ...]; ``attn.wo``; the FFN's / the experts' ``mlp.*down`` or
# ``mlp.*out``), so none needs a switch in the program or a hook in a
# reference. ``python -m chipbench.reference.compare --control <name>``.

def _on_layers(params, fn):
    """``params`` with ``fn(names, leaf)`` applied to every leaf under
    ``layers`` (``names``: the leaf's keys below ``layers``)."""
    def visit(path, a):
        return fn(tuple(k.key for k in path), a)

    return dict(params, layers=jax.tree_util.tree_map_with_path(
        visit, params["layers"]))


def _is_down(names) -> bool:
    return names == ("attn", "wo") or (
        names[0] == "mlp" and names[-1].endswith(("down", "out")))


def drop_layer(params, i):
    """Layer ``i`` adds nothing to the residual stream: its attention's
    and its FFN's / experts' output projections (and output bias) zeroed."""
    return _on_layers(params, lambda n, a: a.at[int(i)].set(0)
                      if _is_down(n) else a)


def drop_experts(params, i):
    """Layer ``i``'s experts add nothing: their down matrices [n_layers,
    n_experts, ffn, d_model] zeroed. Attention stays."""
    hit = []

    def fn(n, a):
        if n[0] == "mlp" and _is_down(n) and a.ndim == 4:
            hit.append(n)
            return a.at[int(i)].set(0)
        return a

    out = _on_layers(params, fn)
    if not hit:
        raise ValueError("drop_experts: no expert matrices in these params")
    return out


def scale_layers(params, factor):
    """Every leaf under ``layers`` times ``factor``."""
    return _on_layers(params, lambda n, a: a * float(factor))


def _rounded(exponent_bits: int, mantissa_bits: int, top: float | None):
    """Every matrix rounded to a narrower float format and back, by
    ``lax.reduce_precision``: a pair of converts (``astype`` there and
    back) is REMOVED by XLA's TPU compiler, which may keep excess
    precision (on the v5e both ``float8_e4m3fn`` and ``bfloat16`` round
    trips read bit for bit what the sound weights read; PERF.md section 6,
    PR 34). With ``top``, one scale a leaf puts the leaf's largest weight
    at ``top``, the format's largest number: N(0, 0.02) weights as they
    are fall under a 4-bit exponent's normal range, which no float8 path
    would do; the scaled rendering is the mildest (2.6% rms error a
    weight; bfloat16 0.17%)."""
    def one(a):
        if not jnp.issubdtype(a.dtype, jnp.floating) or a.ndim < 2:
            return a
        s = jnp.ones((), a.dtype)
        if top is not None:     # a leaf of zeros (a bias) keeps the scale 1
            largest = jnp.abs(a).max()
            s = jnp.where(largest > 0, largest / top, 1.0).astype(a.dtype)
        return jax.lax.reduce_precision(a / s, exponent_bits,
                                        mantissa_bits) * s

    return lambda params, _value=None: jax.tree.map(one, params)


# name -> (the side whose params it changes, the transformation)
CONTROLS = {
    "drop_layer": ("program", drop_layer),
    "drop_experts": ("program", drop_experts),
    "scale_layers": ("program", scale_layers),
    # a precision lower than any configuration states
    # (4 exponent bits, 3 of mantissa; 240 is that format's largest)
    "float8_weights": ("program", _rounded(4, 3, 240.0)),
    # NOT a fault: this one should read about what the sound program
    # reads, and says how much of a reading is rounding of the weights
    "bf16_weights": ("reference", _rounded(8, 7, None)),
}


def apply_control(control: str | None, params):
    """``control`` is ``name`` or ``name=value`` -> (params for the
    program, params for the reference)."""
    if not control:
        return params, params
    name, _, value = control.partition("=")
    if name not in CONTROLS:
        raise ValueError(f"no control {name!r}; there are {sorted(CONTROLS)}")
    side, fn = CONTROLS[name]
    changed = jax.jit(lambda p: fn(p, value or None))(params)
    return (changed, params) if side == "program" else (params, changed)
