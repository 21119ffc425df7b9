"""Model step: device time of the leaf instructions under the scope
``attn_diff`` (``ray_tpu/models/mixers.py``: what differential attention
adds outside the kernels: the ``lambda``s, ``a_1 - lambda a_2``, the pair
norm and its scale; every pass, the attention and the cross layers), a run
of ``jit_train_step`` in the traced window, mean over the chips
(``_named_scope``)."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "attn_diff")
