"""Model step: device time of the leaf instructions under the scopes
``attn_layout`` and ``attn_delta`` of ``ray_tpu/ops/attention.py`` (what
the Pallas kernels' operand layout costs around them: every move
between ``[B,T,H,D]`` and ``[BH,T,D]``, the logsumexp between a column and
dense, and the backward's float32 ``rowsum(dO * O)``; every pass), a run
of ``jit_train_step`` in the traced window, mean over the chips
(``_attn_parts``)."""

from chipbench.layer_metrics import _attn_parts


def read(run: dict):
    return _attn_parts.step_ms(run, _attn_parts.LAYOUT, _attn_parts.DELTA)
