"""Model step: device time of the leaf instructions under the sub-scope
``attn_full`` (the attention of the GLOBAL layers: projections, no
positional encoding, the plain causal kernels, output projection; every
pass), a run of ``jit_train_step`` in the traced window, mean over the
chips (``_attn_scopes``)."""

from chipbench.layer_metrics import _attn_scopes


def read(run: dict):
    return _attn_scopes.step_ms(run, _attn_scopes.FULL)
