"""Which part of the model a device instruction belongs to, and which pass.

The program runs every part of its train step under a ``jax.named_scope``
(``ray_tpu/models/transformer.py``, ``SCOPES``). jax writes the scope
path into each HLO instruction's ``op_name``, and the profiler keeps it
in the ``.xplane.pb``: every ``XLA Ops`` event points at an event
METADATA record whose stats hold ``tf_op`` (the ``op_name``, with ``:``
and an op type behind it), ``hlo_category``, ``flops``,
``bytes_accessed`` and ``source``. ``jax.profiler.ProfileData``, which
``chipbench/xplane.py`` reads with, yields only the events' own stats, not
the metadata's. So this module reads the metadata itself: ``XSpace`` is
plain protobuf, and the few fields needed are walked with a wire-format
reader below that uses the standard library alone (no protobuf, xprof or
tensorflow package; a plane's lines, nearly all of the file, are skipped
unread). A metadata record's ``name`` is the instruction's whole text, the
same string ``xplane.Event.name`` holds, so the two join by name within a
device plane.

THE RULE (settled on compiled CPU programs in
``tests/test_model_scopes.py``, jax 0.9.0). An ``op_name`` is a path,
``jit(train_step)/transpose(jvp(layers))/while/body/closed_call/
checkpoint/rematted_computation/attn/dot_general``; one instruction can
carry several joined by ``;`` (the first that names a part counts).

* part: a scope shows either as a path element (inside a scan body or
  outside any differentiation: ``.../closed_call/attn/...``,
  ``jit(train_step)/optimizer/...``) or inside a transform's brackets
  (``jvp(head_loss)``, ``transpose(jvp(layers))``) when it was opened
  directly in the differentiated function. The path is cut at ``/``,
  ``(`` and ``)``, and the LAST piece that is a scope is the part: the
  innermost, so ``layers`` is what an instruction gets only when no part
  of a block claims it (the scan's reads of the stacked weights and writes
  of the stacked gradients). No piece a scope: ``unscoped``.
* pass: ``recompute`` if the path has ``rematted_computation``
  (``jax.checkpoint`` re-running its forward inside the backward pass:
  the layers with ``remat=True``, the chunked cross entropy with
  ``ce_impl="checkpoint"``); else ``backward`` if it has ``transpose(``;
  else ``forward``. With ``remat=False`` nothing is ``recompute`` and the
  backward reads ``transpose(jvp(attn))``. A ``custom_vjp``'s forward rule
  runs under ``jvp(...)`` and its backward rule under ``transpose(jvp(
  ...))``: the ``fused`` cross entropy computes its gradients in its
  forward scan, so they count as ``head_loss`` ``forward``. ``optimizer``
  and ``grad_accum`` lie outside the differentiation: ``forward``.

A fusion has ONE ``tf_op``, its root's: a fusion that mixes parts goes to
one of them, whole.

An executable that jax loaded from a persistent compile cache keeps the
metadata of the tree that compiled it (jax's cache key leaves metadata
out). The program therefore puts the identity of its model file into the
key (``SCOPES_ID`` in ``ray_tpu/models/transformer.py``), so its step is
never another tree's. A program from before the scopes has none, whatever
the cache: every reader here then returns None rather than call the whole
step unscoped.
"""

from __future__ import annotations

import os
import re

from chipbench import xplane

# ray_tpu.models.transformer.SCOPES, spelled out: the benchmark also runs
# against a program that has none.
PARTS = ("embed", "layers", "attn_norm", "attn", "mlp_norm", "mlp", "moe",
         "final_norm", "head_loss", "grad_accum", "optimizer")
UNSCOPED = "unscoped"
PASSES = ("forward", "recompute", "backward")
STEP_PROGRAM = "jit_train_step"
_CUT = re.compile(r"[/()]")


def classify(op_name: str) -> tuple[str, str]:
    """(part, pass) of one ``op_name`` / ``tf_op`` by THE RULE above."""
    names = [n for n in op_name.split(";") if n]
    chosen, part = (names[0] if names else ""), UNSCOPED
    for name in names:
        found = [p for p in _CUT.split(name) if p in PARTS]
        if found:
            chosen, part = name, found[-1]
            break
    if "rematted_computation" in chosen:
        return part, "recompute"
    return part, ("backward" if "transpose(" in chosen else "forward")


# -- the .xplane.pb's event metadata -----------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for anything with a length or a fixed width."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    """The value (field 2) of one protobuf map entry."""
    return next((v for f, v in _fields(entry) if f == 2), None)


def op_names(path: str) -> dict[str, dict[str, str]]:
    """device plane name -> {instruction text -> ``tf_op``} from the
    file's event metadata. XSpace.planes = 1; XPlane: name 2,
    event_metadata 4, stat_metadata 5 (maps); XEventMetadata: name 2,
    stats 5; XStat: metadata_id 1, str_value 5, ref_value 7 (a stat
    metadata id whose name is the string); XStatMetadata: id 1, name 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f2, v in _fields(plane):
            if f2 == 2:
                name = _text(v)
            elif f2 == 4:
                events.append(_map_value(v))
            elif f2 == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not xplane.DEVICE_PLANE.match(name):
            continue
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        by_text = out.setdefault(name, {})
        for meta in events:
            text, op = "", ""
            for f3, v in _fields(meta):
                if f3 == 2:
                    text = _text(v)
                elif f3 == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op:
                        op = (_text(stat[5]) if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if op:
                by_text[text] = op
    return out


# -- the table ----------------------------------------------------------------

def table(trace: xplane.Trace, names: dict[str, dict[str, str]]) -> dict:
    """{(part, pass): seconds} of the leaf instructions, a chip (summed
    over the traced window, averaged over the chips). The rows add up to
    the leaves' total time, which is ``xplane.busy_s`` (one instruction at
    a time on a core)."""
    out: dict[tuple[str, str], float] = {}
    for dev in trace.devices:
        by_text = names.get(dev.name, {})
        for e in dev.ops:
            key = classify(by_text.get(e.name, ""))
            out[key] = out.get(key, 0.0) + e.dur
    k = max(1, len(trace.devices)) * 1e9
    return {key: t / k for key, t in out.items()}


def step_runs(trace: xplane.Trace) -> float:
    """Runs of the train step in the traced window, a chip."""
    if not trace.devices:
        return 0.0
    return sum(sum(m.name.startswith(STEP_PROGRAM) for m in d.modules)
               for d in trace.devices) / len(trace.devices)


def _describe(tab: dict, busy: float) -> str:
    parts = sorted({p for p, _ in tab},
                   key=lambda p: -sum(t for (q, _), t in tab.items()
                                      if q == p))
    rows = "; ".join(
        f"{p} " + " / ".join(f"{tab.get((p, ps), 0.0):.4f}" for ps in PASSES)
        for p in parts)
    total = sum(tab.values())
    return (f"scopes: s a chip in the traced window by part, {' / '.join(PASSES)}"
            f": {rows}; sum {total:.4f} s against busy {busy:.4f} s")


def of_run(run: dict) -> dict | None:
    """The run's table and step count, made once and kept on the run:
    ``{"table": {(part, pass): s}, "runs": n}``; None where there is no
    trace, no run of the train step in it, or no instruction with a scope
    (a program without scopes). The first call prints the whole table on a
    ``[chipbench]`` line."""
    if "scopes" in run:
        return run["scopes"]
    run["scopes"] = None
    trace, notes = run.get("trace"), run.get("notes", [])
    path = run.get("trace_dir") and xplane.find_xplane(run["trace_dir"])
    if trace is None or not path:
        return None
    tab = table(trace, op_names(path))
    runs = step_runs(trace)
    notes.append(_describe(tab, xplane.busy_s(trace)))
    notes.append(f"scopes: {runs:g} runs of {STEP_PROGRAM} a chip; trace "
                 f"file {os.path.getsize(path)} bytes")
    if not runs:
        return None
    if all(part == UNSCOPED for part, _ in tab):
        notes.append("scopes: no instruction carries a scope of the program "
                     "(it has none): step_*_ms left out")
        return None
    run["scopes"] = {"table": tab, "runs": runs}
    return run["scopes"]


def step_ms(run: dict, parts: tuple[str, ...] | None = None,
            passes: tuple[str, ...] | None = None) -> float | None:
    """Device milliseconds a run of the train step spent in instructions
    of these parts (None: any) and passes (None: any), a chip."""
    found = of_run(run)
    if found is None:
        return None
    seconds = sum(t for (part, ps), t in found["table"].items()
                  if (parts is None or part in parts)
                  and (passes is None or ps in passes))
    return 1e3 * seconds / found["runs"]
