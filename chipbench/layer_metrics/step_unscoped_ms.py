"""Model step: device time of the leaf instructions that carry none of the
program's scopes, a run of ``jit_train_step`` in the traced window, mean
over the chips: the error bar of the attribution by scope
(``chipbench/scopes.py``). Instructions of other programs in the window
(a batch's transfer, a cast) are in it."""

from chipbench import scopes


def read(run: dict):
    return scopes.step_ms(run, parts=(scopes.UNSCOPED,))
