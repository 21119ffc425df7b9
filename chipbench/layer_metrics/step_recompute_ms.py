"""Model step: device time of the leaf instructions that re-run the forward
inside the backward pass (``rematted_computation`` in their ``op_name``),
whatever their part, a run of ``jit_train_step`` in the traced window,
mean over the chips (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(run: dict):
    return scopes.step_ms(run, passes=("recompute",))
