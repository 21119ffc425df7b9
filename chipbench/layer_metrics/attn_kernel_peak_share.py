"""Model step: the Pallas attention kernels' model FLOPs a step
(``flops/<arch>.py``'s ``attention_kernel_flops_per_step``: the seven
matmuls of forward + backward over the VISIBLE (query, key) pairs of
every layer, window or not; the recomputed forward not counted) over
their device time (``_attn_scopes.kernel_step_ms``: forward, recomputed
forward, dq, dk / dv) and the chip's bf16 peak: the roofline share of
the attention kernels, windowed and plain together. Compute is their
bound (``attention_kernel_bytes_per_step``: some 500 FLOP a byte against
the chip's 240). A kernel that masks the tiles a window hides, where it
should skip them, spends the time and earns no FLOPs here, so it reads
low; the recompute and the hidden half of every diagonal tile are in the
denominator only, so the share cannot pass 100."""

from chipbench import spec
from chipbench.layer_metrics import _attn_scopes


def read(run: dict):
    ms = _attn_scopes.kernel_step_ms(run)
    if not ms or not run.get("peaks"):
        return None
    cell = run["cell"]
    flops = spec.load_part("flops", cell["config_data"]["arch"])
    per_step = getattr(flops, "attention_kernel_flops_per_step", None)
    if per_step is None:
        return None
    cfg = spec.model_config(cell["config_data"])
    t = cell["traffic_data"]
    work = per_step(cfg, t["seq_len"], t["rows_per_chip"])
    return 100.0 * work / (ms / 1e3) / run["peaks"]["bf16_flops_per_s"]
