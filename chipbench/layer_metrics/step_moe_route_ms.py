"""Model step: device time of the leaf instructions under ``moe_router``,
``moe_dispatch`` and ``moe_combine`` (router matmul, softmax, top-k and
loss terms; sort and gather into expert order; gather back, gates and
sum; every pass): what routing costs beyond the experts' arithmetic. A
run of ``jit_train_step`` in the traced window, mean over the chips
(``_moe_scopes``)."""

from chipbench.layer_metrics import _moe_scopes


def read(run: dict):
    return _moe_scopes.step_ms(run, (_moe_scopes.ROUTER, _moe_scopes.DISPATCH,
                                     _moe_scopes.COMBINE))
