"""Model step: device time of the leaf instructions under the scope
``kda_conv`` (``ray_tpu/ops/linear_attention.py``: the three causal
depthwise convolutions over 4 positions of a KDA layer's q, k and v, their
SiLU and the l2 norms of q and k; every pass, all KDA layers), a run of
``jit_train_step`` in the traced window, mean over the chips
(``_named_scope``). Bound by memory: elementwise passes over [T, H, 128]."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "kda_conv")
