"""Model step: device time of the leaf instructions under the scope
``attn_gqa`` (k and v repeated to the query heads, the repeat's
recompute and the sum over the copies that is its gradient; every pass),
a run of ``jit_train_step`` in the traced window, mean over the chips
(``_attn_parts``). 0 where XLA folded every such instruction into a
fusion rooted under another name."""

from chipbench.layer_metrics import _attn_parts


def read(run: dict):
    return _attn_parts.step_ms(run, _attn_parts.GQA)
