"""Model step: device time of the leaf instructions under the scope
``moe_experts`` (the three grouped matmuls and the activation between
them; forward, recompute and backward), a run of ``jit_train_step`` in the
traced window, mean over the chips (``_moe_scopes``)."""

from chipbench.layer_metrics import _moe_scopes


def read(run: dict):
    return _moe_scopes.step_ms(run, (_moe_scopes.EXPERTS,))
