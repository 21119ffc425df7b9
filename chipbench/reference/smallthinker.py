"""Plain reference of the ``smallthinker`` arch (SmallThinker-21B-A3B,
arXiv:2507.20984; ``PowerInfer/SmallThinker-21BA3B-Instruct``'s
``config.json``, and for what that file has no key the model code as the
catalog describes it: "sparse ReGLU; router placed before attention").
One layer, no bias anywhere, no QK-norm, RMSNorm eps 1e-6; layer ``i`` is
GLOBAL where ``i % 4 == 0`` (``sliding_window_layout[i] == 0`` and
``rope_layout[i] == 0``), else WINDOWED:

    a = RMSNorm1(x)
    r = Wr a                                64 router logits, from the FIRST norm
    q, k, v = Wq a, Wk a, Wv a              28 query heads, 4 key / value heads, 128 wide
    windowed: q, k = rope(q), rope(k)       theta 1.5e6; key j visible to query i iff 0 <= i - j < 4096
    global:   no positional encoding        key j visible iff j <= i
    h = x + Wo softmax(q k^T / sqrt(128)) v each query head on key head (head // 7)
    u = RMSNorm2(h)
    S = top6(r);  g = softmax(r[S])         float32, over the six chosen logits
    y = h + sum over e in S of g_e Wdown_e (relu(Wgate_e u) * Wup_e u)

then a final RMSNorm and an untied head. Every assignment is computed: no
capacity, no drop.

THE SHARE. The parameters hold ``H``, 16 consecutive experts of the 64
(``cfg.experts_held`` = (rank, of): experts ``rank * 64 / of`` onward).
The router, the top-6 and the softmax stay over all 64; the sum runs over
``S`` intersected with ``H`` only. What the absent experts would add is
left out, and that partial ``y`` is what the next layer reads: the
reference of one expert-parallel rank without its exchange, not of the
whole model. With ``experts_held`` None it IS the whole model.

Training loss = cross entropy + 0.01 x balance (no z term), where a
layer's balance = 64 x sum_e f_e P_e over ALL 64 experts (``f_e`` the
share of the batch's (token, choice) assignments that went to expert e,
summing to 1; ``P_e`` the mean over tokens of softmax(r)_e), the mean
over layers. ``config.json`` gives no weight: Switch Transformer's 0.01.

float32 throughout under ``default_matmul_precision("highest")``; no
kernel, no sort, nothing of ``ray_tpu/ops/``: attention in blocks of
``QUERY_BLOCK`` queries against the key prefix, the window a mask over
it; a token meets its experts through a [tokens, 64] matrix of gates
that is zero where the expert was not chosen, in a loop over the HELD
experts (each multiplies every token; the zero gates drop what was not
routed to it). One layer at a time over the program's stacked weights.

Departures from the published model: rows are seeded tokens (no
documents, so no segment mask and no padding); weights are seeded
N(0, 0.02), not the checkpoint's; secondary experts (no key in
``config.json``) are not modelled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common
from chipbench.reference.llama import _rope

BALANCE_WEIGHT = 0.01     # Switch Transformer's; config.json has none
EPS = 1e-6                # rms_norm_eps
QUERY_BLOCK = 512         # [28, 512, 16384] float32 scores are 0.9 GB


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _attention(q, k, v, window):
    """q, k, v [B, T, H, Dh] -> [B, T, H, Dh]: key j visible to query i
    iff ``j <= i`` and, with a window, ``i - j < window``."""
    T, Dh = q.shape[1], q.shape[-1]
    outs = []
    for s in range(0, T, QUERY_BLOCK):
        e = min(T, s + QUERY_BLOCK)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:e], k[:, :e]) / Dh ** 0.5
        d = jnp.arange(s, e)[:, None] - jnp.arange(e)[None, :]
        visible = d >= 0 if window is None else (d >= 0) & (d < window)
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(scores, axis=-1), v[:, :e]))
    return jnp.concatenate(outs, axis=1)


def _experts(u, gates, mlp):
    """u [N, D], gates [N, Eh] (zero where not chosen) -> [N, D]."""
    def one_expert(out, expert):
        gate_e, w_gate, w_up, w_down = expert
        act = jax.nn.relu(u @ w_gate) * (u @ w_up)
        return out + gate_e[:, None] * (act @ w_down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          (gates.T, mlp["w_gate"], mlp["w_up"],
                           mlp["w_down"]))
    return out


def _layer(x, lp, n_heads: int, theta: float, top_k: int, window,
           with_rope: bool, first_held: int):
    """One layer on x [B, T, D] -> (y, balance)."""
    B, T, D = x.shape
    a = _rms(x, lp["ln1"]["w"])
    r = a.reshape(B * T, D) @ lp["router"]["w"]               # [N, 64]
    q = jnp.einsum("btd,dhk->bthk", a, lp["attn"]["wq"])
    k = jnp.einsum("btd,dhk->bthk", a, lp["attn"]["wk"])
    v = jnp.einsum("btd,dhk->bthk", a, lp["attn"]["wv"])
    if with_rope:
        q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    h = x + jnp.einsum("bthk,hkd->btd", _attention(q, k, v, window),
                       lp["attn"]["wo"])
    u = _rms(h, lp["ln2"]["w"]).reshape(B * T, D)
    E = r.shape[-1]
    top_r, top_e = jax.lax.top_k(r, top_k)
    g = jax.nn.softmax(top_r, axis=-1)                        # over the six
    chosen = jax.nn.one_hot(top_e, E, dtype=jnp.float32)      # [N, k, 64]
    gates = (chosen * g[..., None]).sum(1)                    # [N, 64]
    held = lp["mlp"]["w_gate"].shape[0]
    out = _experts(u, gates[:, first_held:first_held + held], lp["mlp"])
    f = chosen.sum((0, 1)) / (B * T * top_k)                  # sums to 1
    balance = E * jnp.sum(f * jax.nn.softmax(r, axis=-1).mean(0))
    return h + out.reshape(B, T, D), balance


def _run(params, tokens, cfg):
    """(logits, balance): the router term as the mean over layers."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6, 7))
    rank, of = cfg.experts_held or (0, 1)
    first_held = rank * (cfg.n_experts // of)
    pattern = cfg.layer_pattern
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        balance = 0.0
        for i in range(cfg.n_layers):
            windowed, with_rope = pattern[i % len(pattern)]
            x, b_i = layer(x, _common.layer_slice(params["layers"], i),
                           cfg.n_heads, float(cfg.rope_theta),
                           cfg.expert_top_k,
                           cfg.sliding_window if windowed else None,
                           bool(with_rope), first_held)
            balance = balance + b_i / cfg.n_layers
        x = _rms(x, params["final_norm"]["w"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32), balance


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    return _run(params, tokens, cfg)[0]


def loss(params, tokens, cfg):
    """The whole training loss on rows ``tokens`` [B, T + 1]."""
    logits, balance = _run(params, tokens[:, :-1], cfg)
    return _common.next_token_loss(logits, tokens) + BALANCE_WEIGHT * balance
