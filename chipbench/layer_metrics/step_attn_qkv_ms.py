"""Model step: device time of the leaf instructions under the scope
``attn_qkv`` (the projections into attention: the q / k / v einsums, the
weights' casts, the fused-QKV concat and split; latent attention: the
query's einsum alone, the latent's down- and up-projection are
``mla_latent``; every pass), a run of ``jit_train_step`` in the traced
window, mean over the chips (``_attn_parts``)."""

from chipbench.layer_metrics import _attn_parts


def read(run: dict):
    return _attn_parts.step_ms(run, _attn_parts.QKV)
