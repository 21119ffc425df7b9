"""Model step: device time of the leaf instructions under the sub-scope
``attn_window`` (the attention of the WINDOWED layers: projections, RoPE,
the kernels that skip the tiles outside the window, output projection;
every pass), a run of ``jit_train_step`` in the traced window, mean over
the chips (``_attn_scopes``). All the windowed layers together: divide by
their number (three of a period of four) to set one beside a global
layer's ``step_attn_full_ms``."""

from chipbench.layer_metrics import _attn_scopes


def read(run: dict):
    return _attn_scopes.step_ms(run, _attn_scopes.WINDOW)
