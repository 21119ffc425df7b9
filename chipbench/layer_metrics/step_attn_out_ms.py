"""Model step: device time of the leaf instructions under the scope
``attn_out`` (the projection out of attention: the ``wo`` einsum, its
cast, the residual add; every pass), a run of ``jit_train_step`` in the
traced window, mean over the chips (``_attn_parts``)."""

from chipbench.layer_metrics import _attn_parts


def read(run: dict):
    return _attn_parts.step_ms(run, _attn_parts.OUT)
