"""Device time of the leaf instructions under ONE named scope the program
opens somewhere inside a part (``mla_latent`` inside ``attn``,
``moe_shared`` inside ``moe``), read the way ``_moe_scopes`` reads its
four: by the ``op_name`` that ``scopes.classify`` would choose, a run of
``jit_train_step`` in the traced window, mean over the chips. Not a
metric itself."""

from __future__ import annotations

from chipbench import scopes, xplane
from chipbench.layer_metrics import _moe_scopes


def _table(run: dict) -> dict:
    """{path element: s a chip in the traced window} over every element of
    every leaf instruction's scope path, made once and kept on the run."""
    if "named_scopes" not in run:
        names = scopes.op_names(xplane.find_xplane(run["trace_dir"]))
        ns: dict[str, float] = {}
        for dev in run["trace"].devices:
            by_text = names.get(dev.name, {})
            for e in dev.ops:
                for piece in set(_moe_scopes._pieces(by_text.get(e.name, ""))):
                    ns[piece] = ns.get(piece, 0.0) + e.dur
        k = max(1, len(run["trace"].devices)) * 1e9
        run["named_scopes"] = {piece: t / k for piece, t in ns.items()}
    return run["named_scopes"]


def step_ms(run: dict, name: str) -> float | None:
    """Milliseconds a run of the train step, a chip, in leaf instructions
    whose scope path holds ``name`` (every pass). None where the run has
    no readable trace (``scopes.of_run``) or no instruction carries the
    name: a program, or a model, without that scope."""
    found = scopes.of_run(run)
    if found is None:
        return None
    seconds = _table(run).get(name)
    if not seconds:
        return None
    run.get("notes", []).append(
        f"scope {name}: {seconds:.4f} s a chip in the traced window")
    return 1e3 * seconds / found["runs"]
