"""Device time of attention by the KIND of layer (the sub-scopes
``attn_full`` and ``attn_window`` that ``ray_tpu/models/transformer.py``
opens inside ``attn`` for a model with a layer pattern), and of the
Pallas attention kernels among them, read the way ``_moe_scopes`` reads
its own: leaf instructions of the traced window, by the ``op_name`` that
``scopes.classify`` would choose, a run of ``jit_train_step``, mean over
the chips. ``scopes.py`` gives all of these to ``attn``; this looks one
level further in. A kernel is an instruction whose ``op_name`` ends in
``pallas_call`` (a Pallas call keeps the scope path it was traced
under). Not a metric itself."""

from __future__ import annotations

from chipbench import scopes, xplane
from chipbench.layer_metrics import _moe_scopes

FULL, WINDOW = SUB_SCOPES = ("attn_full", "attn_window")
KERNEL = "pallas_call"


def _table(run: dict) -> dict | None:
    """{sub-scope: s a chip, (sub-scope, KERNEL): s a chip} of the traced
    window, made once and kept on the run; None with no readable trace."""
    if scopes.of_run(run) is None:
        return None
    if "attn_scopes" not in run:
        names = scopes.op_names(xplane.find_xplane(run["trace_dir"]))
        seconds: dict = {}
        for dev in run["trace"].devices:
            by_text = names.get(dev.name, {})
            for e in dev.ops:
                pieces = _moe_scopes._pieces(by_text.get(e.name, ""))
                for sub in set(pieces).intersection(SUB_SCOPES):
                    seconds[sub] = seconds.get(sub, 0.0) + e.dur
                    if any(p.startswith(KERNEL) for p in pieces):
                        key = (sub, KERNEL)
                        seconds[key] = seconds.get(key, 0.0) + e.dur
        k = max(1, len(run["trace"].devices)) * 1e9
        run["attn_scopes"] = {name: t / k for name, t in seconds.items()}
        run.get("notes", []).append(
            "attn scopes: s a chip in the traced window: " + (", ".join(
                f"{n if isinstance(n, str) else n[0] + ' kernels'} {t:.4f}"
                for n, t in sorted(run["attn_scopes"].items(), key=str))
                or "neither of them on any instruction"))
    return run["attn_scopes"]


def step_ms(run: dict, sub_scope: str) -> float | None:
    """Milliseconds a run of the train step, a chip, in leaf instructions
    under ``sub_scope`` (every pass). None where the run has no readable
    trace or no instruction carries either sub-scope: a program, or a
    model, without them."""
    table = _table(run)
    if not table:
        return None
    return 1e3 * table.get(sub_scope, 0.0) / run["scopes"]["runs"]


def kernel_step_ms(run: dict) -> float | None:
    """The same for the Pallas kernels under either sub-scope (forward,
    recomputed forward, dq, dk / dv); None where there is none."""
    table = _table(run)
    if not table:
        return None
    seconds = sum(t for key, t in table.items() if isinstance(key, tuple))
    return 1e3 * seconds / run["scopes"]["runs"] if seconds else None
