"""Model step: device time of the leaf instructions under the scope
``moe_shared`` (the shared expert: one gated FFN every token passes
through beside its routed experts; every pass), a run of
``jit_train_step`` in the traced window, mean over the chips
(``_named_scope``)."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "moe_shared")
