"""Model step: the gated delta rule's model FLOPs a step
(``flops/<arch>.py``'s ``kda_core_flops_per_step``: counted from the
RECURRENCE, three products with the ``dk x dv`` state a token and head,
forward + twice that backward; the recomputed forward, the decay's
multiplies and everything a chunked form adds are not counted) over its
device time (``step_kda_core_ms``) and the chip's bf16 peak: the share of
the peak the recurrence runs at, whatever implements it. The chunked
form does MORE arithmetic than it is credited with here and runs its
forward twice (remat), so the share cannot pass 100; a few percent is a
scan of small products bound by latency, not by the MXU."""

from chipbench import spec
from chipbench.layer_metrics import step_kda_core_ms


def read(run: dict):
    ms = step_kda_core_ms.read(run)
    if not ms or not run.get("peaks"):
        return None
    cell = run["cell"]
    flops = spec.load_part("flops", cell["config_data"]["arch"])
    per_step = getattr(flops, "kda_core_flops_per_step", None)
    if per_step is None:
        return None
    cfg = spec.model_config(cell["config_data"])
    t = cell["traffic_data"]
    work = per_step(cfg, t["seq_len"], t["rows_per_chip"])
    return 100.0 * work / (ms / 1e3) / run["peaks"]["bf16_flops_per_s"]
