"""Model step: model FLOPs a token (``chipbench/flops/<arch>.py``,
recomputation not counted) x tokens a step / the median step time (host
clock over fetch groups, so a stall while the profiler starts does not
count) / (chips x the chip's bf16 peak)."""

from chipbench import spec
from chipbench.layer_metrics import train_step_ms


def read(run: dict):
    step_ms = train_step_ms.read(run)
    if step_ms is None or not run.get("peaks"):
        return None
    cell = run["cell"]
    cfg = spec.model_config(cell["config_data"])
    flops = spec.load_part("flops", cell["config_data"]["arch"])
    per_token = flops.train_flops_per_token(
        cfg, cell["traffic_data"]["seq_len"])
    rate = run["train"]["tokens_per_step"] / (step_ms / 1e3) / cell["chips"]
    return 100.0 * per_token * rate / run["peaks"]["bf16_flops_per_s"]
