"""Plain reference of the ``nemotron_h`` arch (NVIDIA-Nemotron-3-Nano-30B-
A3B, ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s ``config.json``; the
Nemotron-H report, arXiv:2504.03624; what that file has no key for is the
public implementations', ``transformers`` ``models/bamba`` / ``mamba2``
``torch_forward`` for the mixer and ``models/deepseek_v3`` for the router).
No bias on any projection, eps 1e-5, untied head, NO position embedding
anywhere. Every layer is ONE sublayer behind one RMSNorm, ``x <- x +
f(norm(x; w))``, by the letters of ``hybrid_override_pattern`` (``M E M E
M * E M E`` are the published layers 0-8). With ``u = norm(x)`` of the
residual stream ``x``, D = 2,688:

``M``, a Mamba-2 state-space mixer, 64 heads of 64 channels, state 128,
``B`` and ``C`` in 8 groups (head ``h`` reads group ``h // 8``):

    [z | xBC | dt] = u W_z, u W_xbc, u W_dt           4,096 | 6,144 | 64
    xBC' = silu(conv4(xBC) + b_conv)     causal, depthwise, the token and the
                                         three before it
    xs, B, C = split(xBC')               64 x 64 | 8 x 128 | 8 x 128
    Delta_t = softplus(dt_t + dt_bias)   A = -exp(A_log)      a head each
    H_t = exp(Delta_t A) H_{t-1} + Delta_t xs_t (x) B_t       H_0 = 0, R^{64 x 128}
    y_t = H_t C_t + D xs_t
    x = x + W_o [ norm_group(y_t * silu(z_t)) * w_n ]         the mean square
                                         over each of 8 groups of 512 channels,
                                         the gate AHEAD of the norm

The state is carried TOKEN BY TOKEN (``recurrence``: one ``lax.scan`` step
a position, no chunks), so nothing here shares a form with
``ray_tpu/ops/state_space.py``.

``*``, attention, 32 query heads on 2 key / value heads of 128:

    x = x + W_o softmax_causal(q k^T / sqrt(128)) v     query head n on key
                                                        head n // 16

``E``, experts: ``s = sigmoid(W_r u)`` over 128 in float32; ``S`` = the 6
largest of ``s + b``; ``g = 2.5 x s[S] / sum(s[S])``; an expert is ``W_down
relu(W_up u)^2``, no gate projection:

    x = x + sum over e in S of g_e expert_1856,e(u) + expert_3712,shared(u)

Then the final norm and the head.

THE SHARE. The parameters hold ``H``, consecutive experts of the 128
(``cfg.experts_held`` = (rank, of)); router, top-6 and gates stay over all
128 and the sum runs over ``S`` intersected with ``H``; what the absent
experts would add is left out, and that partial ``x`` is what the next
layer reads. The shared expert is added whole. With ``experts_held`` None
it IS the whole model.

The training loss is the next-token cross entropy and nothing else (the
recipe states no router term: the rest is 0), so this module exports no
``loss``.

float32 throughout under ``default_matmul_precision("highest")``; nothing
of ``ray_tpu/ops/``. Scores are materialised a block of queries at a time;
a token meets its experts through a [tokens, held] matrix of gates that is
zero where the expert was not chosen, in a loop over the HELD experts. One
layer at a time over the program's stacks, each stacked over the layers
that HOLD it: ``ln1``, ``attn.wo`` and the mixer's own leaves (``ssm.*``,
``mha.*``) over the mixer layers (of that kind), ``ln2``, ``router`` and
``mlp`` over the expert layers; ``attn.wo`` [32, 128, D] is a state-space
layer's [64 x 64, D] by rows.

Departures from the public implementation: rows are seeded tokens (no
segment mask, no padding); weights are seeded, not the checkpoint's
(``rescale_prenorm_residual`` is an init scale and is not applied); the
one published ``in_proj`` is three leaves by what its columns make: the
same mathematics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common

EPS = 1e-5                  # norm_eps / layer_norm_epsilon
QUERY_BLOCK = 1024


def _norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def conv4(x, w, bias):
    """Causal depthwise convolution with a bias: x [B, T, C], w [K, C]; the
    LAST tap multiplies the token itself."""
    taps, length = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x) + bias
    for j in range(taps):
        back = taps - 1 - j                         # positions behind
        out = out + w[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :length]
    return out


def recurrence(xs, delta, a, b, c, skip):
    """The state-space recurrence one position a step. xs [B, T, H, P],
    delta [B, T, H], a [H] (< 0), b and c [B, T, H, N] (already a head's),
    skip [H] -> y [B, T, H, P]; the state [B, H, P, N] starts at 0."""
    def token(state, ops):
        x_t, d_t, b_t, c_t = ops
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + jnp.einsum("bhp,bhn->bhpn", d_t[..., None] * x_t, b_t))
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    state = jnp.zeros((*xs.shape[:1], *xs.shape[2:], b.shape[-1]),
                      jnp.float32)
    _, y = jax.lax.scan(token, state, jax.tree.map(
        lambda v: jnp.moveaxis(v, 1, 0), (xs, delta, b, c)))
    return jnp.moveaxis(y, 0, 1) + skip[:, None] * xs


def _state_space(u, w, groups: int, state: int):
    """The mixer of ``u`` [B, T, D] up to (not with) W_o: [B, T, H * P]."""
    B, T, D = u.shape
    heads, width = w["w_z"].shape[1:]
    z = u @ w["w_z"].reshape(D, -1)
    xbc = jax.nn.silu(conv4(u @ w["w_xbc"], w["conv_w"], w["conv_b"]))
    inner, wide = heads * width, groups * state
    xs = xbc[..., :inner].reshape(B, T, heads, width)
    b, c = (jnp.repeat(part.reshape(B, T, groups, state), heads // groups,
                       axis=2)
            for part in (xbc[..., inner:inner + wide],
                         xbc[..., inner + wide:]))
    delta = jax.nn.softplus(u @ w["w_dt"] + w["dt_bias"])
    y = recurrence(xs, delta, -jnp.exp(w["A_log"]), b, c, w["D"])
    gated = (y.reshape(B, T, inner) * jax.nn.silu(z)).reshape(
        B, T, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + EPS)
    return normed.reshape(B, T, inner) * w["o_norm"]


def _attention(u, w):
    """The attention mixer of ``u`` up to (not with) W_o: [B, T, H * Dh];
    the scores a block of queries at a time."""
    q, k, v = (jnp.einsum("btd,dhk->bthk", u, w[name])
               for name in ("wq", "wk", "wv"))
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    outs = []
    for s in range(0, T, QUERY_BLOCK):
        e = min(T, s + QUERY_BLOCK)
        qb = q[:, s:e].reshape(B, e - s, KV, H // KV, Dh)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k[:, :e]) / Dh ** 0.5
        visible = jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None]
        p = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", p, v[:, :e]).reshape(
            B, e - s, H * Dh))
    return jnp.concatenate(outs, axis=1)


def _expert(u, w_up, w_down):
    return jnp.square(jax.nn.relu(u @ w_up)) @ w_down


def _experts(u, lp, top_k: int, scale: float, first_held: int):
    """The expert sublayer's output on ``u`` [N, D]: the HELD experts'
    part of the routed sum and the shared expert."""
    mlp = lp["mlp"]
    s = jax.nn.sigmoid(u @ lp["router"]["w"])                    # [N, 128]
    _, chosen = jax.lax.top_k(s + lp["router"]["b"], top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    gates = (jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32)
             * (scale * picked / picked.sum(-1, keepdims=True))[..., None]
             ).sum(1)                                            # [N, 128]
    held = mlp["w_up"].shape[0]

    def one_expert(out, expert):
        gate_e, w_up, w_down = expert
        return out + gate_e[:, None] * _expert(u, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (gates[:, first_held:first_held + held].T, mlp["w_up"],
         mlp["w_down"]))
    return out + _expert(u, mlp["shared_w_up"], mlp["shared_w_down"])


def _layer(x, lp, kind: str, groups: int, state: int, top_k: int,
           scale: float, first_held: int):
    """One single-sublayer block on x [B, T, D]."""
    B, T, D = x.shape
    if kind == "ffn":
        u = _norm(x, lp["ln2"]["w"]).reshape(B * T, D)
        return x + _experts(u, lp, top_k, scale, first_held).reshape(B, T, D)
    u = _norm(x, lp["ln1"]["w"])
    o = (_state_space(u, lp["ssm"], groups, state) if kind == "ssm"
         else _attention(u, lp["mha"]))
    return x + o @ lp["attn"]["wo"].reshape(-1, D)


# which layers hold a subtree of the stack (the program's ``_holds``)
_HELD_BY = {"ssm": ("ssm",), "mha": ("attn",), "ln1": ("ssm", "attn"),
            "attn": ("ssm", "attn"), "ln2": ("ffn",), "router": ("ffn",),
            "mlp": ("ffn",)}


def stack_layer(stack, kinds, i: int):
    """Layer ``i`` of a stack whose layers are ``kinds``: of every subtree
    the layer holds, its place among the layers that hold it."""
    return {name: _common.layer_slice(
                sub, sum(k in _HELD_BY[name] for k in kinds[:i]))
            for name, sub in stack.items() if kinds[i] in _HELD_BY[name]}


_jit_layer = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6, 7))


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    rank, of = cfg.experts_held or (0, 1)
    static = (cfg.ssm_groups, cfg.ssm_state, cfg.expert_top_k,
              float(cfg.expert_gate_scale), rank * (cfg.n_experts // of))
    kinds = list(cfg.layer_mixers)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        for i, kind in enumerate(kinds):
            x = _jit_layer(x, stack_layer(params["layers"], kinds, i), kind,
                           *static)
        x = _norm(x, params["final_norm"]["w"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32)
