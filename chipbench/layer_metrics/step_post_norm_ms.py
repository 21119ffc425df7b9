"""Model step: device time of the leaf instructions under the scope
``post_norm`` (the RMSNorm on each sublayer's OUTPUT, attention's and the
FFN's / the experts', and the residual add behind it; every pass), a run
of ``jit_train_step`` in the traced window, mean over the chips
(``_named_scope``)."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "post_norm")
