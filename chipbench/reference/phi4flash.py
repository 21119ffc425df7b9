"""Plain reference of the ``phi4flash`` arch (Phi-4-mini-flash-reasoning,
``microsoft/Phi-4-mini-flash-reasoning``'s ``config.json``; the architecture
is SambaY, arXiv:2507.06607; what that file has no key for is the public
implementation's, the repository's ``modeling_phi4flash.py``: its Mamba
defaults, its ``nn.Linear(..., bias=True)``, its ``FlashDiffCustomAttention``).
Every layer is ``x <- x + mixer(LN(x))``, ``x <- x + MLP(LN(x))``: LayerNorm
with weight AND bias, eps 1e-5; the MLP ``W_down (silu(u W_gate) * u W_up)``
without a bias; tied embedding and head; NO positional encoding anywhere.
With ``u = LN(x)`` of the 2,560-wide stream, the mixer by PUBLISHED layer
number ``l`` (from 0; ``cfg.first_layer`` is the first layer's):

``l`` even, <= 16, a Mamba-1 selective scan (arXiv:2312.00752), inner width
5,120, state 16, ``dt_rank`` 160:

    [x | z] = u W_x, u W_z                           5,120 | 5,120
    x' = silu(conv4(x) + b_conv)         causal, depthwise, the token and the
                                         three before it
    [dt_low | B_t | C_t] = x' W_low      160 | 16 | 16
    Delta_t = softplus(dt_low W_dt + b_dt)           ONE step a channel
    A = -exp(A_log)                                  [5,120, 16]
    H_t = exp(Delta_t (x) A) * H_{t-1} + (Delta_t x'_t) (x) B_t    H_0 = 0
    y_t = H_t C_t + D x'_t
    x = x + W_o (y * silu(z))

The state is carried TOKEN BY TOKEN (``recurrence``: one ``lax.scan`` step a
position on a [5,120, 16] state), so nothing here shares a form with
``ray_tpu/ops/state_space.py``. Each such layer's ``y`` (with the ``D``
term, AHEAD of the gate) is the MEMORY ``m`` its later readers see: the
last one's, layer 16's.

``l`` odd, <= 17, differential attention (arXiv:2410.05258), 40 query and
20 key / value heads of 64, a window of 512 keys (the query's own among
them) for ``l`` <= 15, full causal at 17:

    q, k, v = u W_q + b_q, u W_k + b_k, u W_v + b_v
    heads pair up INTERLEAVED: pair n is heads 2 n and 2 n + 1; 20 query
    pairs (q1, q2), 10 key pairs (k1, k2), 10 values 128 wide (a pair's two
    value heads side by side); query pair n reads key / value pair n // 2
    a_i = softmax(q_i k_i^T / 8, causal[, window]) v          i = 1, 2
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
    lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)
    o = (1 - lambda_init(l)) * rmsnorm_128(a_1 - lambda a_2) * w_sub
    x = x + W_o o + b_o

Each such layer's (k1, k2, v) are the keys and values its later readers
see: the last one's, layer 17's.

``l`` even, >= 18, a gated memory unit: ``x = x + W_2 (m * silu(u W_1))``.

``l`` odd, >= 19, differential cross-attention: ``q = u W_q + b_q`` alone,
against layer 17's keys and values, full causal, its own ``lambda``
vectors, ``w_sub``, ``W_o``, ``b_o`` and ``lambda_init`` by its own ``l``.

Then the final LayerNorm and the tied head. The training loss is the
next-token cross entropy and nothing else, so this module exports no
``loss``.

float32 throughout under ``default_matmul_precision("highest")``; nothing
of ``ray_tpu/ops/``. The two softmax maps are materialised a block of
queries at a time against the keys the block can see (``_softmax_map``:
``_common.causal_attention`` has no window, so the block loop is written
out here with one). One layer at a time over the program's stacks, each
stacked over the layers that HOLD it (``_HELD_BY``).

Departures from the public implementation: rows are seeded tokens (no
segment mask, no padding); weights are seeded, not the checkpoint's; the
published ``in_proj`` (``[x | z]``), ``Wqkv`` and the MLP's ``[gate | up]``
are leaves by what their columns make: the same mathematics. Projections
are taken whole and the ACTIVATIONS split into pairs, as the
implementation's ``reshape`` does (the program splits the weights).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common

EPS = 1e-5                  # layer_norm_eps, and the pair norm's
QUERY_BLOCK = 1024


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * p["w"] + p["b"]


def conv(x, w, bias):
    """Causal depthwise convolution with a bias: x [B, T, C], w [K, C]; the
    LAST tap multiplies the token itself."""
    taps, length = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x) + bias
    for j in range(taps):
        back = taps - 1 - j                         # positions behind
        out = out + w[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :length]
    return out


def recurrence(x, delta, a, b, c, skip):
    """The selective scan one position a step. x, delta [B, T, C], a [C, N]
    (< 0), b and c [B, T, N] (every channel's), skip [C] -> y [B, T, C];
    the state [B, C, N] starts at 0."""
    def token(state, ops):
        x_t, d_t, b_t, c_t = ops
        state = (jnp.exp(d_t[..., None] * a) * state
                 + (d_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.einsum("bcn,bn->bc", state, c_t)

    state = jnp.zeros((*x.shape[::2], a.shape[1]), jnp.float32)
    _, y = jax.lax.scan(token, state, jax.tree.map(
        lambda v: jnp.moveaxis(v, 1, 0), (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1) + skip * x


def _mamba(u, w):
    """A Mamba-1 mixer of ``u`` [B, T, D] -> (what joins the stream, the
    scan's output ``y``: the memory)."""
    rank, state = w["w_dt"].shape[0], w["A_log"].shape[1]
    x = jax.nn.silu(conv(u @ w["w_x"], w["conv_w"], w.get("conv_b", 0.0)))
    low = x @ w["w_low"]
    delta = jax.nn.softplus(low[..., :rank] @ w["w_dt"] + w["dt_bias"])
    y = recurrence(x, delta, -jnp.exp(w["A_log"]),
                   low[..., rank:rank + state], low[..., rank + state:],
                   w["D"])
    return (y * jax.nn.silu(u @ w["w_z"])) @ w["wo"], y


def _softmax_map(q, k, v, window):
    """q, k [B, T, H, Dh], v [B, T, H, Dv] -> [B, T, H, Dv]: key j visible
    to query i iff ``j <= i`` and, with a window, ``i - j < window``; a
    block of queries against the keys it can see."""
    T, Dh = q.shape[1], q.shape[-1]
    outs = []
    for s in range(0, T, QUERY_BLOCK):
        e = min(T, s + QUERY_BLOCK)
        lo = 0 if window is None else max(0, s - window + 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:e], k[:, lo:e])
        d = jnp.arange(s, e)[:, None] - jnp.arange(lo, e)[None, :]
        visible = d >= 0 if window is None else (d >= 0) & (d < window)
        p = jax.nn.softmax(jnp.where(visible[None, None],
                                     scores / Dh ** 0.5, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, lo:e]))
    return jnp.concatenate(outs, axis=1)


def _pairs(a):
    """[B, T, 2 n, Dh] -> (the pairs' first heads, their second)."""
    B, T, H, Dh = a.shape
    a = a.reshape(B, T, H // 2, 2, Dh)
    return a[:, :, :, 0], a[:, :, :, 1]


def _keys_values(u, w):
    """(k1, k2 [B, T, KV / 2, Dh], v [B, T, KV / 2, 2 Dh]) of ``u``."""
    k = jnp.einsum("btd,dhk->bthk", u, w["wk"]) + w["bk"]
    v = jnp.einsum("btd,dhk->bthk", u, w["wv"]) + w["bv"]
    B, T, KV, Dh = v.shape
    return (*_pairs(k), v.reshape(B, T, KV // 2, 2 * Dh))


def _differential(u, w, kv, number, window):
    """Differential attention of ``u`` against the keys and values ``kv``
    up to (not with) W_o: [B, T, H * Dh]."""
    q1, q2 = _pairs(jnp.einsum("btd,dhk->bthk", u, w["wq"]) + w["bq"])
    rep = q1.shape[2] // kv[0].shape[2]
    k1, k2, v = (jnp.repeat(a, rep, axis=2) for a in kv)
    a1, a2 = _softmax_map(q1, k1, v, window), _softmax_map(q2, k2, v, window)
    init = 0.8 - 0.6 * jnp.exp(-0.3 * number)
    lq1, lk1, lq2, lk2 = w["lambdas"]           # one leaf [4, Dh]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init
    d = a1 - lam * a2
    o = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + EPS)
    o = (1.0 - init) * o * w["sub_norm"]
    return o.reshape(*o.shape[:2], -1)


def _layer(x, lp, memory, number, kind: str, window):
    """One block on x [B, T, D] -> (x, the memories it hands out)."""
    D = x.shape[-1]
    u = _layer_norm(x, lp["ln1"])
    wrote = {}
    if kind == "ssm1":
        out, wrote["m"] = _mamba(u, lp["ssm1"])
    elif kind == "gmu":
        out = (memory["m"] * jax.nn.silu(u @ lp["gmu"]["w1"])) @ lp["gmu"]["w2"]
    else:
        own = lp["mha" if kind == "attn" else "cross"]
        if kind == "attn":
            wrote["kv"] = _keys_values(u, own)
        o = _differential(u, own, wrote.get("kv", memory.get("kv")), number,
                          window)
        out = o @ lp["attn"]["wo"].reshape(-1, D) + lp["attn"]["bo"]
    x = x + out
    u = _layer_norm(x, lp["ln2"])
    mlp = lp["mlp"]
    return x + (jax.nn.silu(u @ mlp["w_gate"]) * (u @ mlp["w_up"])
                ) @ mlp["w_down"], wrote


# which layers hold a subtree of the stack (the program's ``_holds``)
_HELD_BY = {"ssm1": ("ssm1",), "gmu": ("gmu",), "mha": ("attn",),
            "cross": ("cross",), "attn": ("attn", "cross")}


def stack_layer(stack, kinds, i: int):
    """Layer ``i`` of a stack whose layers are ``kinds``: of every subtree
    the layer holds, its place among the layers that hold it."""
    return {name: _common.layer_slice(
                sub, sum(k in _HELD_BY.get(name, kinds) for k in kinds[:i]))
            for name, sub in stack.items()
            if kinds[i] in _HELD_BY.get(name, kinds)}


_jit_layer = jax.jit(_layer, static_argnums=(4, 5))


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    kinds = list(cfg.layer_mixers)
    first = cfg.first_layer or 0
    memory = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        for i, kind in enumerate(kinds):
            windowed, _ = cfg.layer_pattern[(first + i)
                                            % len(cfg.layer_pattern)]
            window = cfg.sliding_window if windowed and kind == "attn" else None
            x, wrote = _jit_layer(
                x, stack_layer(params["layers"], kinds, i), memory,
                jnp.float32(first + i), kind, window)
            memory = {**memory, **wrote}
        x = _layer_norm(x, jax.tree.map(lambda a: a.astype(jnp.float32),
                                        params["final_norm"]))
        return x @ params["embed"]["tokens"].astype(jnp.float32).T
