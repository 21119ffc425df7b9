"""Model step: device time of the leaf instructions under BOTH
``attn_linear`` and ``attn_core``: the gated delta rule of the KDA layers
and nothing else (``ray_tpu/ops/linear_attention.py``
``gated_delta_rule``: the operands' layout moves, the intra-chunk
matrices and the triangular inverse, the scan over chunks that carries
the state, and in the backward the same again with its reverse scan;
every pass, all KDA layers), a run of ``jit_train_step`` in the traced
window, mean over the chips. ``step_attn_core_ms`` holds this AND the
latent layer's kernels. Read the way ``_named_scope`` reads one name: by
the ``op_name`` that ``scopes.classify`` would choose."""

from chipbench import scopes, xplane
from chipbench.layer_metrics import _moe_scopes

BOTH = {"attn_linear", "attn_core"}


def read(run: dict):
    found = scopes.of_run(run)
    if found is None:
        return None
    if "kda_core_s" not in run:
        names = scopes.op_names(xplane.find_xplane(run["trace_dir"]))
        seconds = sum(
            e.dur for dev in run["trace"].devices for e in dev.ops
            if BOTH <= set(_moe_scopes._pieces(
                names.get(dev.name, {}).get(e.name, ""))))
        run["kda_core_s"] = seconds / (max(1, len(run["trace"].devices)) * 1e9)
        run.get("notes", []).append(
            f"scope attn_linear + attn_core: {run['kda_core_s']:.4f} s a "
            f"chip in the traced window")
    if not run["kda_core_s"]:
        return None
    return 1e3 * run["kda_core_s"] / found["runs"]
