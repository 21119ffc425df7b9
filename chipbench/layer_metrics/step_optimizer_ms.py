"""Model step: device time of the leaf instructions under ``optimizer``
(update + apply) or ``grad_accum``, a run of ``jit_train_step`` in the
traced window, mean over the chips (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(run: dict):
    return scopes.step_ms(run, parts=("optimizer", "grad_accum"))
