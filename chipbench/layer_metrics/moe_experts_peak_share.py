"""Model step: the experts' model FLOPs a step (``flops/<arch>.py``'s
``experts_train_flops_per_token``: the grouped matmuls, forward +
backward, recomputation not counted) over ``step_moe_experts_ms`` and the
chip's bf16 peak: the roofline share of the grouped matmul. Compute is
its bound: about 400 FLOP a byte of rows and weights at OLMoE's shapes,
against the chip's 240. Time spent recomputing is in the denominator
only, so the share cannot pass 100."""

from chipbench import spec
from chipbench.layer_metrics import step_moe_experts_ms


def read(run: dict):
    ms = step_moe_experts_ms.read(run)
    if not ms or not run.get("peaks"):
        return None
    cell = run["cell"]
    flops = spec.load_part("flops", cell["config_data"]["arch"])
    per_token = getattr(flops, "experts_train_flops_per_token", None)
    if per_token is None:
        return None
    cfg = spec.model_config(cell["config_data"])
    per_step = per_token(cfg) * run["train"]["tokens_per_step"] / cell["chips"]
    return 100.0 * per_step / (ms / 1e3) / run["peaks"]["bf16_flops_per_s"]
