"""Model FLOPs of attention's PROJECTIONS alone, from a configuration's
widths (see ``_common``): what a token passes through on its way into and
out of attention, whatever the arch. Plain attention: q ``d x h x dh``,
k and v ``d x kv x dh`` each, out ``h x dh x d``. Latent attention: q
``d x h x (nope + rope)``, the down-projection ``d x (latent + rope)``,
the up-projection ``latent x h x (nope + v)``, out ``h x v x d`` (counted
as the layer is written, the latent not absorbed). Every layer has them.
Not an arch's file: ``attn_outside_peak_share`` reads it for any."""

from __future__ import annotations

from chipbench.flops import _common


def projection_params(cfg) -> float:
    """Parameters of one layer's attention projections."""
    d, h = cfg.d_model, cfg.n_heads
    if cfg.kv_latent is not None:
        return (d * h * cfg.head_dim
                + d * (cfg.kv_latent + cfg.d_head_rope)
                + cfg.kv_latent * h * (cfg.d_head_nope + cfg.d_head_v)
                + h * cfg.d_head_v * d)
    dh = cfg.head_dim
    return d * h * dh + 2 * d * cfg.kv_heads * dh + h * dh * d


def train_flops_per_step(cfg, seq_len: int, rows: int) -> float:
    """Forward + backward of every layer's projections on ``rows`` rows
    a chip; recomputation not counted (``_common``'s rule)."""
    return (_common.train_flops_per_token(
        cfg.n_layers * projection_params(cfg), 0.0) * seq_len * rows)
