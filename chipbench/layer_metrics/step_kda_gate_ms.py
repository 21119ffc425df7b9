"""Model step: device time of the leaf instructions under the scope
``kda_gate`` (``ray_tpu/ops/linear_attention.py``: the decay's and the
output gate's low-rank maps, ``beta``'s projection, softplus and exp, the
head norm of the delta rule's output and its product with the gate, the
``kda_log_decay_min`` counter's reduction; every pass, all KDA layers), a
run of ``jit_train_step`` in the traced window, mean over the chips
(``_named_scope``)."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "kda_gate")
