"""Model step: device time of the leaf instructions under the scope
``attn_core`` (the one ``attention(...)`` call, everything between its
operands and its output: on the kernel path the Pallas kernels, the
layout moves and ``delta`` around them and the sum of the heads'
rotary-key gradients; on the materialised path the score blocks, the
softmax and ``p v``; every pass), a run of ``jit_train_step`` in the
traced window, mean over the chips (``_attn_parts``)."""

from chipbench.layer_metrics import _attn_parts


def read(run: dict):
    return _attn_parts.step_ms(run, _attn_parts.CORE)
