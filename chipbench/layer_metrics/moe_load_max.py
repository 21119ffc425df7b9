"""Model step: the fullest expert's assignments over the mean, in the
fullest layer of the LAST step (``moe_load_max`` in the step's metrics
dict; 1.0 is balance; over the HELD experts where some are held), from
``step_metrics`` in the train report. None where the step reports none."""

from chipbench.layer_metrics import _step_metrics


def read(run: dict):
    return _step_metrics.scalar(run, "moe_load_max")
