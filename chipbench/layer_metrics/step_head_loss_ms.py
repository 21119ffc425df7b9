"""Model step: device time of the leaf instructions under ``final_norm``
or ``head_loss`` (head matmul and cross entropy; every pass), a run of
``jit_train_step`` in the traced window, mean over the chips
(``chipbench/scopes.py``)."""

from chipbench import scopes


def read(run: dict):
    return scopes.step_ms(run, parts=("final_norm", "head_loss"))
