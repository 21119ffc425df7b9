"""Model step: how far the share of the LAST step's (token, choice)
assignments that went to an expert this rank holds (``moe_held_share`` in
the step's metrics dict, the mean over the layers) lies from balance, 1 /
ranks: |share - 1 / ranks|, lower is nearer the deployment the cell
stands for and the FLOPs ``flops/<arch>.py`` counts (the experts at
balance). The share itself stays in the report (``step_metrics``) and in
the run's notes. One step's value: it swings from step to step. None
where the step reports no such counter."""

from chipbench import spec
from chipbench.layer_metrics import _step_metrics


def read(run: dict):
    share = _step_metrics.scalar(run, "moe_held_share")
    held = share is not None and spec.model_config(
        run["cell"]["config_data"]).experts_held
    return abs(share - 1.0 / held[1]) if held else None
