"""Plain reference of the ``afmoe`` arch (Trinity-Mini,
``arcee-ai/Trinity-Mini``'s ``config.json``; what that file has no key for
is the public implementation's, ``transformers``
``models/afmoe/modeling_afmoe.py``). No bias on any projection, every norm
an RMSNorm with a learned weight and eps 1e-5, untied head. Published
layer ``i`` (from 0) is FULL where ``(i + 1) % 4 == 0``
(``global_attn_every_n_layers`` 4, ``layer_types``), else SLIDING; the
first ``num_dense_layers`` are DENSE, every later one an EXPERT layer.
With ``x`` the residual stream, D = 2048:

    h = norm_in(x)
    q = rmsnorm_head(Wq h)   32 heads x 128     k = rmsnorm_head(Wk h)   4 x 128     v = Wv h   4 x 128
                             q_norm and k_norm each ONE 128-wide weight, every head of the layer's
    sliding: q, k = rope(q), rope(k)            theta 1e4 over the whole 128, halves paired;
                                                key j visible to query i iff i - 2048 < j <= i
    full:    no positional encoding at all      key j visible iff j <= i
    a = softmax(q k^T / sqrt(128)) v            query head n on key head n // 8
    a = a * sigmoid(Wg h)                       Wg: 2048 -> 32 x 128
    x = x + norm_post_attn(Wo a)
    m = norm_pre_mlp(x)
    dense layer:   f = SwiGLU_6144(m)
    expert layer:  s = sigmoid(Wr m)            128 scores, float32
                   S = top8(s + b)              b enters the CHOICE and nothing else
                   g = 2.826 x s[S] / (sum(s[S]) + 1e-20)
                   f = SwiGLU_1024,shared(m) + sum over e in S of g_e SwiGLU_1024,e(m)
    x = x + norm_post_mlp(f)

The embedding's rows enter the stream times sqrt(2048) (``mup_enabled``);
logits are ``W_head norm_final(x)``, not scaled.

THE SHARE. The parameters hold ``H``, consecutive experts of the 128
(``cfg.experts_held`` = (rank, of)). The router, the top-8 and the gates
stay over all 128; the sum runs over ``S`` intersected with ``H`` only,
and the shared expert is added whole. What the absent experts would add
is left out (AHEAD of ``norm_post_mlp``, which norms the partial sum),
and that partial ``x`` is what the next layer reads: one expert-parallel
rank without its exchange. With ``experts_held`` None it IS the whole
model. ``cfg.first_layer`` is the published number of the first layer the
parameters hold; its ``n_dense_layers`` first layers are the dense ones.

The training loss is the next-token cross entropy and nothing else (the
bias-balanced recipe has no router term): this module exports no
``loss``. The bias ``b`` is moved by the train step's rule and by no
gradient; here it is read as it stands.

float32 throughout under ``default_matmul_precision("highest")``; no
kernel, no sort, nothing of ``ray_tpu/``: scores are materialised a block
of ``QUERY_BLOCK`` queries at a time against the keys that block can see,
the mask written from the definition over absolute positions; a token
meets its experts through a [tokens, 128] matrix of gates that is zero
where the expert was not chosen, in a loop over the HELD experts. One
layer at a time over the program's stacked weights.

Departures from the public implementation: rows are seeded tokens (no
segment mask, no padding); weights are seeded N(0, 0.02), ``b`` too (the
checkpoint's ``expert_bias`` starts at zeros), not the checkpoint's; the
gate's sigmoid and the router's are float32 like everything here (the
implementation's run in the activations' dtype); RoPE pairs the halves
(as the implementation's ``rotate_half`` does).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _common
from chipbench.reference.llama import _rope

EPS = 1e-5                # rms_norm_eps
GLOBAL_EVERY = 4          # global_attn_every_n_layers
ROUTE_EPS = 1e-20         # under the chosen scores' sum (route_norm)
QUERY_BLOCK = 512         # [32, 512, 16384] float32 scores are 1.07 GB


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _attention(q, k, v, window):
    """q [B, T, H, Dh], k and v [B, T, KV, Dh] -> [B, T, H, Dh]: query head
    n reads key head ``n // (H / KV)``; key j is visible to query i iff
    ``j <= i`` and, with a window, ``i - window < j``."""
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    outs = []
    for s in range(0, T, QUERY_BLOCK):
        e = min(T, s + QUERY_BLOCK)
        lo = 0 if window is None else max(0, s - window + 1)
        qb = q[:, s:e].reshape(B, e - s, KV, H // KV, Dh)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k[:, lo:e]) / Dh ** 0.5
        i, j = jnp.arange(s, e)[:, None], jnp.arange(lo, e)[None, :]
        visible = j <= i if window is None else (j <= i) & (i - window < j)
        scores = jnp.where(visible, scores, -jnp.inf)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, axis=-1),
                         v[:, lo:e])
        outs.append(out.reshape(B, e - s, H, Dh))
    return jnp.concatenate(outs, axis=1)


def _experts(u, gates, mlp):
    """u [N, D], gates [N, Eh] (zero where not chosen) -> [N, D]."""
    def one_expert(out, expert):
        gate_e, w_gate, w_up, w_down = expert
        return out + gate_e[:, None] * _swiglu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          (gates.T, mlp["w_gate"], mlp["w_up"],
                           mlp["w_down"]))
    return out


def _mixer(x, lp, window, theta: float):
    """The attention sublayer on x [B, T, D], its output normed and
    added; ``window`` None: a full layer, no RoPE."""
    w = lp["attn"]
    h = _rms(x, lp["ln1"]["w"])
    q = _rms(jnp.einsum("btd,dhk->bthk", h, w["wq"]), w["q_norm"])
    k = _rms(jnp.einsum("btd,dhk->bthk", h, w["wk"]), w["k_norm"])
    v = jnp.einsum("btd,dhk->bthk", h, w["wv"])
    if window is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    a = _attention(q, k, v, window)
    a = a * jax.nn.sigmoid(jnp.einsum("btd,dhk->bthk", h, w["wg"]))
    return x + _rms(jnp.einsum("bthk,hkd->btd", a, w["wo"]),
                    lp["ln1_post"]["w"])


def _ffn(m, lp, dense: bool, top_k: int, gate_scale: float, first_held: int):
    """What the FFN sublayer makes of its normed input m [N, D], AHEAD of
    its output norm: the dense SwiGLU, or the shared expert plus the held
    experts' part of the routed sum."""
    mlp = lp["mlp"]
    if dense:
        return _swiglu(m, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
    s = jax.nn.sigmoid(m @ lp["router"]["w"])                     # [N, 128]
    _, chosen = jax.lax.top_k(s + lp["router"]["b"], top_k)
    picked = jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32).sum(1)
    gates = gate_scale * picked * s / (
        (picked * s).sum(-1, keepdims=True) + ROUTE_EPS)
    held = mlp["w_gate"].shape[0]
    shared = _swiglu(m, mlp["shared_w_gate"], mlp["shared_w_up"],
                     mlp["shared_w_down"])
    return shared + _experts(m, gates[:, first_held:first_held + held], mlp)


def _layer(x, lp, dense: bool, window, theta: float, top_k: int,
           gate_scale: float, first_held: int):
    """One block on x [B, T, D]."""
    x = _mixer(x, lp, window, theta)
    f = _ffn(_rms(x, lp["ln2"]["w"]).reshape(-1, x.shape[-1]), lp, dense,
             top_k, gate_scale, first_held)
    return x + _rms(f.reshape(x.shape), lp["ln2_post"]["w"])


def forward(params, tokens, cfg):
    """float32 logits [B, T, V] of ``tokens`` [B, T]."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6, 7))
    rank, of = cfg.experts_held or (0, 1)
    static = (float(cfg.rope_theta), cfg.expert_top_k,
              float(cfg.expert_gate_scale), rank * (cfg.n_experts // of))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        x = x * cfg.d_model ** 0.5                  # mup_enabled
        for i in range(cfg.n_layers):
            published = (cfg.first_layer or 0) + i
            full = (published + 1) % GLOBAL_EVERY == 0
            dense = i < cfg.n_dense_layers
            stack = params["dense_layers" if dense else "layers"]
            at = i if dense else i - cfg.n_dense_layers
            x = layer(x, _common.layer_slice(stack, at), dense,
                      None if full else cfg.sliding_window, *static)
        x = _rms(x, params["final_norm"]["w"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32)
