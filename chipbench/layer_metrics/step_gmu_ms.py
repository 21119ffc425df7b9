"""Model step: device time of the leaf instructions under the scope ``gmu``
(``ray_tpu/models/mixers.py``: a gated memory unit's two projections and
its product ``m * silu(h W_1)`` with the scan output an earlier layer
handed out; every pass, all such layers), a run of ``jit_train_step`` in
the traced window, mean over the chips (``_named_scope``)."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "gmu")
