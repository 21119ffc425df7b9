"""Runtime: the seconds of ``setup_s`` that lie under NO span of the
program: ``setup_s`` less the measure of the union of

- ``runtime.init`` (the driver: head up, driver attached),
- ``train.fit``'s start to ``train.loop``'s start (run state, the worker
  group, the chip lease and its wait, backend set-up),
- and, in the train worker up to the window's start: ``train.backend_init``
  (the jax import, libtpu opening the chips), every ``jax.compile`` span
  taken from ``lead_s`` before its start (tracing, lowering, then the
  compile or the load from the cache) and every ``data.next_batch``.

What is left is the driver's imports before ``ray_tpu.init``, the
benchmark's reference, weights and moments being made, the warm-up steps
running: work the program opens no span around, because the program does
not do it. The run's start is the window's start less ``run["setup_s"]``:
one host, one clock (``chipbench/timeline.py`` has where the window
starts).

Also appends ONE line to ``run["notes"]``: the phases in a run's order
with their seconds (every second of ``setup_s`` under exactly one name, so
they sum to it) and the three longest uncovered gaps, each named by the
spans on either side of it.

None where the timeline is not whole, or where no ``jax.compile`` span
carries ``lead_s`` (a program from before the set-up spans: what its
timeline leaves uncovered is another quantity); a span that lacks an
attribute counts it as 0.
"""

from chipbench import timeline
from chipbench.layer_metrics import setup_chips_wait_s

FIT_TO_LOOP = "train.fit->train.loop"
LEAD = "jax.compile lead"
# The note's order (a run's), and the order in which a second under two
# names is given to one (a compile inside another jit's trace is a compile).
ORDER = ("runtime.init", FIT_TO_LOOP, "train.backend_init", LEAD,
         "jax.compile", "data.next_batch")
CLAIM = ("runtime.init", FIT_TO_LOOP, "train.backend_init", "jax.compile",
         LEAD, "data.next_batch")


def _intervals(run: dict, t0: float, w0: float):
    """(start, end, phase, label) of every span that covers set-up,
    clipped to [t0, w0], in the timeline's microseconds; and the
    ``jax.compile`` spans' attributes."""
    worker = timeline.train_worker(run)
    found = []
    init = timeline.named(run, "runtime.init")[-1]
    found.append((init["ts"], timeline.end(init), "runtime.init",
                  "runtime.init"))
    fit = timeline.named(run, "train.fit")
    loop = timeline.named(run, "train.loop", worker)[-1]
    if fit:
        found.append((fit[-1]["ts"], loop["ts"], FIT_TO_LOOP,
                      "train.fit..train.loop"))
    for e in timeline.named(run, "train.backend_init", worker):
        found.append((e["ts"], timeline.end(e), e["name"], e["name"]))
    for e in timeline.named(run, "data.next_batch", worker):
        index = (e.get("args") or {}).get("index")
        found.append((e["ts"], timeline.end(e), e["name"],
                      f"data.next_batch({index})"))
    compiles = []
    for e in timeline.named(run, "jax.compile", worker):
        if e["ts"] >= w0:
            continue
        args = e.get("args") or {}
        compiles.append(args)
        label = f"jax.compile({args.get('fun', '')})"
        lead = 1e6 * (args.get("lead_s") or 0.0)
        found.append((e["ts"] - lead, e["ts"], LEAD, label))
        found.append((e["ts"], timeline.end(e), "jax.compile", label))
    clipped = [(max(s, t0), min(e, w0), phase, label)
               for s, e, phase, label in found]
    return sorted(c for c in clipped if c[1] > c[0]), compiles


def _union(intervals) -> tuple[float, list]:
    """The measure of the union of sorted intervals, and the gaps between
    its pieces as (length, label of the span that ends before the gap,
    label of the span that starts after it)."""
    covered, gaps = 0.0, []
    at, last = float("-inf"), None
    for s, e, _, label in intervals:
        if s > at and last is not None:
            gaps.append((s - at, last, label))
        if e > at:
            covered += e - max(s, at)
            at, last = e, label
    return covered, gaps


def read(run: dict):
    w = timeline.window(run)
    setup_s = run.get("setup_s")
    if w is None or setup_s is None:
        return None
    w0 = w[0]
    t0 = w0 - 1e6 * setup_s
    intervals, compiles = _intervals(run, t0, w0)
    if not any("lead_s" in a for a in compiles):
        return None

    # Every second under exactly one name: a phase gets what it adds to
    # the union of the phases that claim before it.
    seconds, claimed, before = {}, [], 0.0
    for phase in CLAIM:
        claimed = sorted(claimed + [i for i in intervals if i[2] == phase])
        now = _union(claimed)[0]
        seconds[phase] = (now - before) / 1e6
        before = now
    covered, gaps = _union(intervals)
    uncovered = setup_s - covered / 1e6

    # The gaps with both ends inside set-up, then the two at its ends.
    first, last = intervals[0], max(intervals, key=lambda i: i[1])
    named = [(length / 1e6, f"{a} -> {b}") for length, a, b in gaps]
    named.append(((first[0] - t0) / 1e6, f"before {first[3]}"))
    named.append(((w0 - last[1]) / 1e6, f"{last[3]} -> the window"))
    longest = sorted(named, reverse=True)[:3]

    waited = setup_chips_wait_s.read(run)
    detail = {
        FIT_TO_LOOP: "" if waited is None else
        f" (worker.hold_chips waited {waited:.2f} s)",
        LEAD: " (trace {:.2f} + lowering {:.2f} s over {} spans)".format(
            sum(a.get("trace_s") or 0.0 for a in compiles),
            sum(a.get("lower_s") or 0.0 for a in compiles), len(compiles)),
    }
    phases = [f"{p} {seconds[p]:.2f} s{detail.get(p, '')}" for p in ORDER]
    total = sum(seconds.values()) + uncovered
    run.setdefault("notes", []).append(
        "set-up under the program's spans: " + ", ".join(phases)
        + f", uncovered {uncovered:.2f} s: sum {total:.2f} s of setup_s "
        f"{setup_s:.2f} s; longest uncovered: "
        + ", ".join(f"{what} {length:.2f} s" for length, what in longest))
    return uncovered
