"""Model step: device time of the leaf instructions whose innermost scope
is ``mlp`` or ``moe`` (every pass), a run of ``jit_train_step`` in the
traced window, mean over the chips (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(run: dict):
    return scopes.step_ms(run, parts=("mlp", "moe"))
