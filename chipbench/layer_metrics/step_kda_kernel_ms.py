"""Model step: device time of the gated delta rule's Pallas kernels: the
leaf instructions under ``attn_linear`` whose ``op_name`` holds a
``pallas_call`` (``ray_tpu/ops/linear_attention.py``: the forward kernel,
again in the layer's recompute, and the backward kernel; all KDA layers),
a run of ``jit_train_step`` in the traced window, mean over the chips.
With ``step_kda_core_ms`` it splits the delta rule into its kernels and
what is still outside them (layout moves, pads, ``beta``'s transposes).
None on a program whose delta rule is plain XLA (no such leaf). Read the
way ``step_kda_core_ms`` reads its two names: by the ``op_name`` that
``scopes.classify`` would choose."""

from chipbench import scopes, xplane
from chipbench.layer_metrics import _attn_parts, _moe_scopes

SCOPE, KERNEL = "attn_linear", _attn_parts.KERNEL


def read(run: dict):
    found = scopes.of_run(run)
    if found is None:
        return None
    if "kda_kernel_s" not in run:
        names = scopes.op_names(xplane.find_xplane(run["trace_dir"]))
        seconds = 0.0
        for dev in run["trace"].devices:
            by_text = names.get(dev.name, {})
            for e in dev.ops:
                pieces = _moe_scopes._pieces(by_text.get(e.name, ""))
                if SCOPE in pieces and any(
                        p.startswith(KERNEL) for p in pieces):
                    seconds += e.dur
        run["kda_kernel_s"] = seconds / (
            max(1, len(run["trace"].devices)) * 1e9)
        run.get("notes", []).append(
            f"scope attn_linear, Pallas kernels: {run['kda_kernel_s']:.4f} s "
            f"a chip in the traced window")
    if not run["kda_kernel_s"]:
        return None
    return 1e3 * run["kda_kernel_s"] / found["runs"]
