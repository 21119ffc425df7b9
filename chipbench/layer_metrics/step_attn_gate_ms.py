"""Model step: device time of the leaf instructions under the scope
``attn_gate`` (the gate on attention's output: its 2048 -> 32 x 128
projection, the sigmoid and the product with what the kernels return,
and their gradients; every pass), a run of ``jit_train_step`` in the
traced window, mean over the chips (``_named_scope``)."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "attn_gate")
