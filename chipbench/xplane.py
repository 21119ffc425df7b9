"""Reduction of a jax profiler trace (``.xplane.pb``) to device metrics.

What a v5e trace holds (looked at by hand, PERF.md section 6): one plane
``/device:TPU:<i>`` per chip with the lines ``Steps``, ``XLA Modules``
(one event per program run, named ``jit_<fn>(<fingerprint>)``),
``XLA Ops`` (one event per HLO instruction as the TensorCore ran it; the
name is the instruction's whole text) and ``Async XLA Ops`` (the span
from an asynchronous ``*-start`` to its ``*-done``: DMA that runs beside
the core). ``/host:CPU`` has a line per host thread; ``python`` carries
``jax.profiler.TraceAnnotation`` spans. Device and host events share one
clock.

A control-flow instruction (``while``, ``conditional``, ``call``: the
layer scan of a train step is a ``while``) is an ``XLA Ops`` event too, and
it spans every instruction of its body, which have events of their own
inside it. Counted as an instruction it would paint its whole span busy and
hide every stall and every collective inside the scan. So only LEAVES
count: an event that encloses another event of its line is a container
and is left out of every sum below (``split_containers``); a loop whose
body the profiler did not record stays a leaf.

Definitions, fixed here so that every PR computes them the same way:

  busy      union of the intervals of the leaf ``XLA Ops`` events (the
            core ran an instruction). Async DMA alone is not "busy".
  idle      1 - busy / window.
  kernel    events whose text has ``custom_call_target="tpu_custom_call"``
            (a Pallas kernel).
  collective exposed
            time in which a collective (``XLA Ops`` or ``Async XLA Ops``
            event whose opcode is all-gather, all-reduce, reduce-scatter,
            collective-permute or all-to-all, plain or -start/-done) is in
            flight and no other leaf ``XLA Ops`` event runs on that
            device.

Numbers are averaged over the device planes. Reading needs only jax.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?$")
# "%name = <type> opcode(operands...)": the type may be a tuple.
_HLO = re.compile(r"^%?(?P<name>\S+) = (?P<type>\(.*?\)|\S+) "
                  r"(?P<opcode>[\w-]+)\(")

Interval = tuple[float, float]  # start, end in ns


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float   # ns
    end: float     # ns
    inside: str = ""   # the control-flow instruction whose body it is in

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def short(self) -> str:
        """``fusion.4`` of ``%fusion.4 = f32[...] fusion(...)``."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def opcode(self) -> str:
        m = _HLO.match(self.name)
        return m.group("opcode") if m else ""

    @property
    def label(self) -> str:
        """A name a reader of the ledger can act on: the enclosing
        control-flow instruction if any (``while.8/``: which layer scan),
        instruction name, opcode (``pallas`` for a kernel), result type."""
        m = _HLO.match(self.name)
        if not m:
            return self.name[:80]
        op = "pallas" if KERNEL_MARK in self.name else m.group("opcode")
        typ = re.sub(r"\{[^}]*\}", "", m.group("type"))
        where = f"{self.inside}/" if self.inside else ""
        return f"{where}{m.group('name')} {op} {typ}"[:96]


@dataclasses.dataclass
class DevicePlane:
    name: str
    all_ops: list[Event]        # the ``XLA Ops`` line as recorded
    async_ops: list[Event]
    modules: list[Event]
    ops: list[Event] = dataclasses.field(init=False)         # leaves
    containers: list[Event] = dataclasses.field(init=False)

    def __post_init__(self):
        self.ops, self.containers = split_containers(self.all_ops)


@dataclasses.dataclass
class Trace:
    devices: list[DevicePlane]
    host: dict[str, list[Event]]   # host thread line -> events


def _events(line) -> list[Event]:
    return [Event(e.name, float(e.start_ns),
                  float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def split_containers(events: list[Event]) -> tuple[list[Event], list[Event]]:
    """(leaves, containers) of one ``XLA Ops`` line. The core runs one
    instruction at a time, so one event lies inside another only where
    the outer one is a control-flow instruction and the inner one belongs
    to its body: an event that encloses the next event (by start, longer
    first) is a container. Every event is told which container it is
    ``inside`` (the innermost)."""
    evs = sorted((e for e in events if e.dur > 0),
                 key=lambda e: (e.start, -e.end))
    leaves = [e for e in events if e.dur <= 0]
    containers, open_ = [], []
    for e, nxt in zip(evs, evs[1:] + [None]):
        while open_ and open_[-1].end <= e.start:
            open_.pop()
        if open_ and e.end <= open_[-1].end:
            e = dataclasses.replace(e, inside=open_[-1].short)
        if nxt is not None and nxt.start < e.end and nxt.end <= e.end:
            containers.append(e)
            open_.append(e)
        else:
            leaves.append(e)
    return leaves, containers


def from_profile_data(pd) -> Trace:
    devices, host = [], {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices.append(DevicePlane(
                plane.name, lines.get(OPS_LINE, []),
                lines.get(ASYNC_LINE, []), lines.get(MODULES_LINE, [])))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host.setdefault(ln.name, []).extend(_events(ln))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile_data(ProfileData.from_file(path))


def trace_dir(cache_dir: str, cell: str) -> str:
    """An empty directory for this cell's trace, at a fixed path."""
    import os
    import shutil

    path = os.path.join(cache_dir, "trace", cell)
    shutil.rmtree(path, ignore_errors=True)
    return path


def start_trace(path: str) -> None:
    """Start the jax profiler as the reduction expects it: device and
    host-runtime events, no Python frames (large, and read by nothing)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``*.xplane.pb`` under a ``start_trace`` directory."""
    import glob
    import os

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


# -- interval arithmetic ------------------------------------------------------

def union(intervals: list[Interval]) -> list[Interval]:
    out: list[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: list[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """The part of union ``a`` not covered by union ``b`` (both merged)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(events: list[Event]) -> list[Interval]:
    return [(ev.start, ev.end) for ev in events]


# -- metrics ------------------------------------------------------------------

def busy_s(trace: Trace) -> float:
    """Seconds in which an instruction ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return sum(total(union(_spans(d.ops)))
               for d in trace.devices) / len(trace.devices) / 1e9


def is_kernel(ev: Event) -> bool:
    return KERNEL_MARK in ev.name


def is_collective(ev: Event) -> bool:
    return bool(COLLECTIVE.match(ev.opcode)
                or COLLECTIVE.match(ev.short.split(".")[0]))


def kernel_events(trace: Trace) -> list[Event]:
    return [e for d in trace.devices for e in d.ops if is_kernel(e)]


def kernel_s(trace: Trace) -> float:
    """Device seconds inside Pallas kernels, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return sum(e.dur for e in kernel_events(trace)) / len(trace.devices) / 1e9


def collective_exposed_s(trace: Trace) -> float:
    """Seconds a collective is in flight with nothing else running on
    that device's core, averaged over the devices."""
    if not trace.devices:
        return 0.0
    out = 0.0
    for d in trace.devices:
        coll = union(_spans([e for e in d.ops + d.async_ops
                             if is_collective(e)]))
        compute = union(_spans([e for e in d.ops if not is_collective(e)]))
        out += total(subtract(coll, compute))
    return out / len(trace.devices) / 1e9


def exposed_collectives(trace: Trace, n: int = 10) -> list[list]:
    """[label, seconds] of the collectives with most time in flight and
    no other instruction on that device's core (summed over runs,
    averaged over devices). Two collectives alone together both count
    the time, so the rows can add up to more than
    ``collective_exposed_s``."""
    agg: dict[str, float] = {}
    for d in trace.devices:
        compute = union(_spans([e for e in d.ops if not is_collective(e)]))
        ends = [c[1] for c in compute]
        for e in d.ops + d.async_ops:
            if is_collective(e):
                i = j = bisect.bisect_right(ends, e.start)
                while j < len(compute) and compute[j][0] < e.end:
                    j += 1
                alone = total(subtract([(e.start, e.end)], compute[i:j]))
                agg[e.label] = agg.get(e.label, 0.0) + alone
    k = max(1, len(trace.devices)) * 1e9
    return [[name, t / k] for name, t in
            sorted(agg.items(), key=lambda kv: -kv[1])[:n] if t > 0]


def top_device_ops(trace: Trace, n: int = 10) -> list[list]:
    """[label, seconds] of the (leaf) instructions that took most device
    time, summed over runs and averaged over devices; they add up to at
    most ``busy_s``."""
    return _by_label([d.ops for d in trace.devices], n)


def container_ops(trace: Trace, n: int = 10) -> list[list]:
    """[label, seconds] of the control-flow instructions left out of
    every sum (a ``while`` is a layer scan): what each spans, body and
    stalls together."""
    return _by_label([d.containers for d in trace.devices], n)


def _by_label(lines: list[list[Event]], n: int) -> list[list]:
    agg: dict[str, float] = {}
    for events in lines:
        for e in events:
            agg[e.label] = agg.get(e.label, 0.0) + e.dur
    k = max(1, len(lines)) * 1e9
    return [[name, t / k] for name, t in
            sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def _most_overlap(events: list[Event], gs: float, ge: float):
    """(event, overlap in ns) of the event that overlaps [gs, ge) most."""
    best, best_ov = None, 0.0
    for e in events:
        ov = min(ge, e.end) - max(gs, e.start)
        if ov > best_ov:
            best, best_ov = e, ov
    return best, best_ov


def idle_gaps(trace: Trace, n: int = 10, min_gap_ns: float = 20_000.0,
              largest: int = 400) -> list[list]:
    """[what the gap waited for, seconds] for the idle time of the first
    device. Each of the ``largest`` gaps between busy intervals is named:
    a gap INSIDE a program's run (an ``XLA Modules`` event) waits for
    nothing on the host, so it goes to ``in <program>: <collective>`` for
    the collective in flight over most of it, else ``in <program>:
    stall``; a gap between programs goes to the host event that overlaps
    it most (``python`` annotations first, then any host thread), or to
    ``unattributed``. The remaining small gaps go to ``short gaps``;
    seconds are summed by name."""
    import numpy as np

    if not trace.devices:
        return []
    dev = trace.devices[0]
    busy = union(_spans(dev.ops))
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])
                   if b[0] - a[1] >= min_gap_ns), reverse=True)
    agg: dict[str, float] = {}
    if len(gaps) > largest:
        agg["short gaps"] = sum(g[0] for g in gaps[largest:])
    lines = [(np.array([e.start for e in evs]), np.array([e.end for e in evs]),
              evs) for _name, evs in sorted(
                  trace.host.items(), key=lambda kv: kv[0] != "python") if evs]
    collectives = [e for e in dev.async_ops if is_collective(e)]
    for length, gs, ge in gaps[:largest]:
        program = next((m for m in dev.modules
                        if m.start <= gs and ge <= m.end), None)
        if program is not None:
            coll, ov = _most_overlap(collectives, gs, ge)
            what = coll.label if coll and ov > 0.5 * length else "stall"
            best = f"in {program.name.split('(')[0]}: {what}"[:80]
        else:
            best, best_ov = "unattributed", 0.0
            for starts, ends, evs in lines:
                ov = np.minimum(ge, ends) - np.maximum(gs, starts)
                i = int(ov.argmax())
                if ov[i] > best_ov:
                    best, best_ov = evs[i].name[:80], float(ov[i])
                if best_ov > 0.5 * length:
                    break
        agg[best] = agg.get(best, 0.0) + length
    return [[name, t / 1e9] for name, t in
            sorted(agg.items(), key=lambda kv: -kv[1])[:n]]
