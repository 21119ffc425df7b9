"""Device time of attention by what it does (the named scopes
``ray_tpu/models/transformer.py`` ``ATTN_PART_SCOPES`` and
``ray_tpu/ops/attention.py`` ``SCOPES`` open inside ``attn``), read the
way ``_attn_scopes`` reads the kind of layer: leaf instructions of the
traced window whose scope path holds ``attn``, by the ``op_name`` that
``scopes.classify`` would choose, a run of ``jit_train_step``, mean over
the chips. A leaf counts under EVERY name on its path (``attn_layout``
lies inside ``attn_core``, latent attention's ``attn_pos`` inside
``mla_latent``), under ``KERNEL`` where it is a Pallas call, and under
``REST`` where it is under none of the seven names, no kernel and not
``mla_latent``. A fusion has ONE ``op_name``, its root's: RoPE fused into
a projection's epilogue goes whole to one name. Not a metric itself."""

from __future__ import annotations

from chipbench import scopes, xplane
from chipbench.layer_metrics import _moe_scopes

# ray_tpu.models.transformer.ATTN_PART_SCOPES + ray_tpu.ops.attention.SCOPES,
# spelled out: the benchmark also runs against a program that has none.
QKV, POS, GQA, CORE, OUT, LAYOUT, DELTA = NAMES = (
    "attn_qkv", "attn_pos", "attn_gqa", "attn_core", "attn_out",
    "attn_layout", "attn_delta")
ATTN, KERNEL, MLA, REST = "attn", "pallas_call", "mla_latent", "attn_rest"
LONGEST = 3     # instructions named under each row of the note


def names_of(op_name: str) -> tuple[list[str], str, str] | None:
    """(the rows a leaf with this ``op_name`` counts under, the ONE row
    that names it innermost, its pass); None where it is not attention's."""
    pieces = _moe_scopes._pieces(op_name)
    if ATTN not in pieces:
        return None
    named = [p for p in pieces if p in NAMES or p == MLA]
    if any(p.startswith(KERNEL) for p in pieces):
        own = KERNEL
    else:
        own = named[-1] if named else REST
    rows = [ATTN, *dict.fromkeys(named)]
    if own not in rows:
        rows.append(own)
    return rows, own, scopes.classify(op_name)[1]


def split(leaves) -> tuple[dict, dict]:
    """({(row, pass): duration}, {row: {label: duration}}) over
    ``(op_name, label, duration)`` leaves. The labels under a row are of
    the leaves that row names INNERMOST (a kernel is the kernels', a
    transpose ``attn_layout``'s, neither ``attn_core``'s), so the longest
    instructions of a row are its own."""
    seconds: dict = {}
    labels: dict = {}
    for op_name, label, dur in leaves:
        found = names_of(op_name)
        if found is None:
            continue
        rows, own, ps = found
        for row in rows:
            seconds[row, ps] = seconds.get((row, ps), 0.0) + dur
        mine = labels.setdefault(own, {})
        mine[label] = mine.get(label, 0.0) + dur
    return seconds, labels


def _describe(seconds: dict, labels: dict, runs: float) -> str:
    def ms(row, passes=scopes.PASSES):
        return 1e3 * sum(seconds.get((row, ps), 0.0) for ps in passes) / runs

    rows = []
    for row in (*NAMES, KERNEL, MLA, REST):
        if not any((row, ps) in seconds for ps in scopes.PASSES):
            continue
        longest = sorted(labels.get(row, {}).items(),
                         key=lambda kv: -kv[1])[:LONGEST]
        rows.append(
            f"{row} {ms(row):.3f} ("
            + " / ".join(f"{ms(row, (ps,)):.3f}" for ps in scopes.PASSES)
            + ")" + "".join(f" [{label} {1e3 * t / runs:.3f}]"
                            for label, t in longest))
    return (f"attn parts: ms a step by scope, {' / '.join(scopes.PASSES)}, "
            f"[its own longest instructions]: attn {ms(ATTN):.3f}; "
            + "; ".join(rows))


def _table(run: dict) -> dict | None:
    """{(row, pass): s a chip} of the traced window, made once and kept
    on the run; None with no readable trace, or where no instruction
    carries any of the seven names (a program without them)."""
    found = scopes.of_run(run)
    if found is None:
        return None
    if "attn_parts" not in run:
        names = scopes.op_names(xplane.find_xplane(run["trace_dir"]))
        seconds, labels = split(
            (names.get(dev.name, {}).get(e.name, ""), e.label, e.dur)
            for dev in run["trace"].devices for e in dev.ops)
        k = max(1, len(run["trace"].devices)) * 1e9
        seconds = {key: t / k for key, t in seconds.items()}
        labels = {row: {label: t / k for label, t in own.items()}
                  for row, own in labels.items()}
        run["attn_parts"] = seconds if any(
            row in NAMES for row, _ in seconds) else None
        run.get("notes", []).append(
            _describe(seconds, labels, found["runs"])
            if run["attn_parts"] else
            "attn parts: none of the seven names on any instruction")
    return run["attn_parts"]


def step_ms(run: dict, *rows: str) -> float | None:
    """Milliseconds a run of the train step, a chip, in leaf instructions
    under ``rows`` (summed; every pass). None on a program without the
    scopes; 0.0 where the program has them and no instruction of this
    model kept the name (its work rode a fusion rooted elsewhere)."""
    table = _table(run)
    if table is None:
        return None
    seconds = sum(t for (row, _), t in table.items() if row in rows)
    return 1e3 * seconds / run["scopes"]["runs"]
