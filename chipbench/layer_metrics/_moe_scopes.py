"""Device time of the dropless mixture of experts' own parts (the named
scopes ``ray_tpu/ops/moe.py`` opens inside the model's ``moe``), read the
way ``chipbench/scopes.py`` reads a part: leaf instructions of the traced
window, by the ``op_name`` that ``scopes.classify`` would choose (the
first of an instruction's names that carries a scope of the program), a
run of ``jit_train_step``, mean over the chips. ``scopes.py`` gives all of
these to ``moe``; this looks one level further in. Not a metric itself."""

from __future__ import annotations

from chipbench import scopes, xplane

ROUTER, DISPATCH, EXPERTS, COMBINE = SUB_SCOPES = (
    "moe_router", "moe_dispatch", "moe_experts", "moe_combine")


def _pieces(op_name: str) -> list[str]:
    """The path elements of the name ``scopes.classify`` goes by."""
    names = [n for n in op_name.split(";") if n]
    for name in names:
        pieces = scopes._CUT.split(name)
        if any(p in scopes.PARTS for p in pieces):
            return pieces
    return scopes._CUT.split(names[0]) if names else []


def step_ms(run: dict, sub_scopes: tuple[str, ...]) -> float | None:
    """Milliseconds a run of the train step, a chip, in leaf instructions
    under any of ``sub_scopes`` (every pass). None where the run has no
    readable trace (``scopes.of_run``) or no instruction of the trace
    carries any of the four sub-scopes: a program without them."""
    found = scopes.of_run(run)
    if found is None:
        return None
    if "moe_scopes" not in run:
        names = scopes.op_names(xplane.find_xplane(run["trace_dir"]))
        seconds: dict[str, float] = {}
        for dev in run["trace"].devices:
            by_text = names.get(dev.name, {})
            for e in dev.ops:
                for sub in set(_pieces(by_text.get(e.name, ""))).intersection(
                        SUB_SCOPES):
                    seconds[sub] = seconds.get(sub, 0.0) + e.dur
        k = max(1, len(run["trace"].devices)) * 1e9
        run["moe_scopes"] = {name: t / k for name, t in seconds.items()}
        run.get("notes", []).append(
            "moe scopes: s a chip in the traced window: " + (", ".join(
                f"{n} {t:.4f}" for n, t in sorted(run["moe_scopes"].items()))
                or "none of them on any instruction"))
    table = run["moe_scopes"]
    if not table:
        return None
    return 1e3 * sum(table.get(s, 0.0) for s in sub_scopes) / found["runs"]
