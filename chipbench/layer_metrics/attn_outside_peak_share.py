"""Model step: the attention projections' model FLOPs a step
(``flops/_attn_proj.py``: q, k, v or q + the latent's down and up, and
out; forward + backward, the recompute NOT counted) over ALL of
attention's device time outside the Pallas kernels (``_attn_parts``:
every leaf under ``attn`` less those that are a ``pallas_call``) and the
chip's bf16 peak. The time is the union of every scope outside the
kernels, whichever name a fusion's root fell to, and holds what is no
matmul at all (positions, the GQA repeat, layout moves, ``delta``, the
recompute), so the share cannot pass 100: it says how much of that time
the projections would need at the peak."""

from chipbench import spec
from chipbench.flops import _attn_proj
from chipbench.layer_metrics import _attn_parts


def read(run: dict):
    attn = _attn_parts.step_ms(run, _attn_parts.ATTN)
    if not attn or not run.get("peaks"):
        return None
    ms = attn - _attn_parts.step_ms(run, _attn_parts.KERNEL)
    cell = run["cell"]
    cfg = spec.model_config(cell["config_data"])
    t = cell["traffic_data"]
    work = _attn_proj.train_flops_per_step(cfg, t["seq_len"],
                                           t["rows_per_chip"])
    return 100.0 * work / (ms / 1e3) / run["peaks"]["bf16_flops_per_s"]
