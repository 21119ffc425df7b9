"""Model step: device time of the leaf instructions under the scope
``attn_cross`` (``ray_tpu/models/mixers.py``: a cross layer's query
projection, its two attention calls against an earlier layer's keys and
values and its differential combine; not its output projection; every
pass, all such layers), a run of ``jit_train_step`` in the traced window,
mean over the chips (``_named_scope``)."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "attn_cross")
