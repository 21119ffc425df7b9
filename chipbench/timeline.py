"""The program's own spans of a run, from the timeline it leaves.

``JaxTrainer.fit()`` writes ``ray_tpu.util.state.timeline()`` to
``<Result.path>/timeline.json`` when it ends and removes a file of that
name when it starts, so a file found after a run is that run's. The train
driver's ``RunConfig`` puts it at
``<root>/.chipbench_cache/train_runs/chipbench-<cell>/timeline.json``. It
is a chrome trace: a list of events; a span of the program
(``ray_tpu.util.tracing.span``) has ``cat == "span"``, ``ph == "X"``,
``name``, ``ts`` and ``dur`` in microseconds on the head's clock, ``tid``
= the recording process's pid, and the span's attributes under ``args``.

The train worker is the process that recorded ``train.loop``. The
measured window starts at the start of its ``data.next_batch`` span with
``index == warmup_steps`` (the loop takes its ``t0`` just before asking
for that batch) and lasts the loop's own ``window_s``.

A program that writes no timeline (the parent of the PR that added it)
leaves no file: every function returns None and no reader raises.

WHOLE OR NOTHING. Spans reach the file by a path that may lose some: a
process buffers 2,048 between two reports to the head, the head hands out
its newest 10,000 events, and the train worker's last report is a cast
that ``fit()``'s kill of the worker can overtake. A count over a timeline
with holes proves nothing, so ``window()`` answers only where the
timeline shows itself whole: ``runtime.init`` is there (the run's oldest
span, the first a capped table loses), ``train.loop`` is there (it closes
last in the worker and travels in that last report), and the window's
``data.next_batch`` spans run from ``warmup_steps`` on without a gap (the
``jax.compile``, ``data.block_wait`` and ``data.to_device`` spans of the
window sit between them in the same buffer). Otherwise it is None, and so
is every reader of the window.
"""

from __future__ import annotations

import json
import os

from chipbench import spec

FILE = "timeline.json"


def path_of(run: dict) -> str:
    return os.path.join(spec.cache_dir(run["root"]), "train_runs",
                        f"chipbench-{run['cell']['name']}", FILE)


def spans(run: dict) -> list[dict] | None:
    """The run's spans sorted by start, read once and kept on the run;
    None when the run left no timeline."""
    if "timeline_spans" not in run:
        try:
            with open(path_of(run)) as f:
                events = json.load(f)
        except (OSError, ValueError):
            events = None
        run["timeline_spans"] = None if events is None else sorted(
            (e for e in events if isinstance(e, dict)
             and e.get("cat") == "span" and e.get("ph") == "X"),
            key=lambda e: e["ts"])
    return run["timeline_spans"]


def named(run: dict, name: str, tid: int | None = None) -> list[dict]:
    return [e for e in spans(run) or [] if e["name"] == name
            and (tid is None or e.get("tid") == tid)]


def train_worker(run: dict) -> int | None:
    """The ``tid`` (process id) of the process that ran the loop."""
    loops = named(run, "train.loop")
    return loops[-1].get("tid") if loops else None


def window(run: dict) -> tuple[float, float] | None:
    """(start, end) of the measured window in the timeline's microseconds;
    None where there is none or the timeline is not whole (see above)."""
    worker = train_worker(run)
    train = run.get("train") or {}
    if (worker is None or not train.get("window_s")
            or not named(run, "runtime.init")):
        return None
    first = run["cell"]["traffic_data"]["warmup_steps"]
    batches = [(e["ts"], (e.get("args") or {}).get("index"))
               for e in named(run, "data.next_batch", worker)]
    batches = [(ts, i) for ts, i in batches if i is not None and i >= first]
    if not batches or batches[0][1] != first:
        return None
    start = batches[0][0]
    stop = start + 1e6 * train["window_s"]
    inside = [i for ts, i in batches if ts <= stop]
    if inside != list(range(first, first + len(inside))):
        return None
    return start, stop


def end(e: dict) -> float:
    return e["ts"] + e["dur"]


def in_window(run: dict, name: str) -> list[dict] | None:
    """The train worker's spans of this name that END inside the window;
    None when there is no window to speak of."""
    w = window(run)
    if w is None:
        return None
    return [e for e in named(run, name, train_worker(run))
            if w[0] <= end(e) <= w[1]]
