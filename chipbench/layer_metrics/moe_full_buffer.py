"""Model step: the share of the LAST step's expert layers in which the
assignments to the experts this rank holds overflowed the row buffer
(twice the rank's balanced share) and the layer took more than one round
of it (``moe_full_buffer`` in the step's metrics dict, the mean over the
layers of 0 or 1; ``ray_tpu/ops/moe.py``). 0 is every layer in one round.
One step's value. None where the step reports no such counter (a program
from before it, a model that holds every expert)."""

from chipbench.layer_metrics import _step_metrics


def read(run: dict):
    return _step_metrics.scalar(run, "moe_full_buffer")
