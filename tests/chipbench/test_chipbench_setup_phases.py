"""The four readers that put a name on every second of ``setup_s``
(``setup_chips_wait_s``, ``setup_backend_s``, ``setup_trace_lower_s``,
``setup_uncovered_s``), on a hand-written ``timeline.json`` whose answers
are computed by hand; no reader raises on a timeline without the spans and
attributes they read (an older program's, a run that left none)."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import spec, timeline

READERS = ("setup_chips_wait_s", "setup_backend_s", "setup_trace_lower_s",
           "setup_uncovered_s")
DRIVER, WORKER, OTHER = 100, 200, 300
S = 1e6     # the timeline counts microseconds
SETUP_S = 20.0


def _span(name, start_s, dur_s, tid, **args):
    return {"cat": "span", "ph": "X", "name": name, "ts": start_s * S,
            "dur": dur_s * S, "pid": 0, "tid": tid, "args": args}


def _timeline():
    """The run starts at 0 s (``setup_s`` 20, the window 20-24 s). Driver:
    init 1-3 s, fit from 3.5 s. Worker: the lease 4-6.6 s, loop from 7 s,
    the backend 7-10 s; compile A 11-13 s with a lead of 1 s; compile B
    14-15 s whose lead of 2.5 s reaches back over A's last 1.5 s; nothing
    15-17 s; a load 17-17.5 s that carries no attributes; two warm-up
    batches; batches 2, 3, 4 in the window."""
    ev = [
        _span("runtime.init", 1.0, 2.0, DRIVER, head="started"),
        _span("train.fit", 3.5, 36.5, DRIVER),
        _span("worker.hold_chips", 4.0, 2.6, WORKER, chips=[0], nodes=1,
              waited_s=2.5),
        _span("train.worker.setup", 6.7, 0.2, WORKER, rank=0),
        _span("train.loop", 7.0, 23.0, WORKER, rank=0),
        _span("train.backend_init", 7.0, 3.0, WORKER, platform="tpu",
              device_kind="TPU v5 lite", devices=1),
        _span("jax.compile", 11.0, 2.0, WORKER, cache="miss", fun="jit(init)",
              trace_s=0.5, lower_s=0.25, lead_s=1.0),
        _span("jax.compile", 14.0, 1.0, WORKER, cache="miss", fun="jit(call)",
              trace_s=1.5, lower_s=0.5, lead_s=2.5),
        _span("jax.compile", 17.0, 0.5, WORKER, cache="hit",
              fun="jit(train_step)"),
        _span("jax.compile", 12.0, 9.0, OTHER, cache="miss", fun="jit(other)",
              trace_s=7.0, lower_s=7.0, lead_s=14.0),        # not ours
        _span("jax.compile", 21.0, 0.25, WORKER, cache="miss", fun="jit(late)",
              trace_s=9.0, lower_s=9.0, lead_s=0.5),         # in the window
        _span("worker.hold_chips", 2.0, 9.0, OTHER, chips=[1], nodes=1,
              waited_s=8.5),                                 # not ours
    ]
    for index, start, dur in ((0, 18.0, 0.25), (1, 19.0, 0.1), (2, 20.0, 0.01),
                              (3, 21.5, 0.01), (4, 23.0, 0.01)):
        ev.append(_span("data.next_batch", start, dur, WORKER, index=index,
                        rows=8))
    return ev


def _run(tmp_path, events=None, setup_s=SETUP_S) -> dict:
    run = {"root": str(tmp_path), "train": {"window_s": 4.0}, "notes": [],
           "setup_s": setup_s,
           "cell": {"name": "hand", "traffic_data": {"warmup_steps": 2}}}
    if events is not None:
        path = timeline.path_of(run)
        os.makedirs(os.path.dirname(path))
        with open(path, "w") as f:
            json.dump(events, f)
    return run


def _read(name: str, run: dict):
    return spec.load_part("layer_metrics", name).read(run)


def _without_the_new(events):
    """The timeline as the program of before these spans leaves it."""
    old = [dict(e, args={k: v for k, v in e["args"].items()
                         if k not in ("trace_s", "lower_s", "lead_s")})
           for e in events]
    return [e for e in old
            if e["name"] not in ("worker.hold_chips", "train.backend_init")]


def test_readers_on_the_hand_written_timeline(tmp_path):
    run = _run(tmp_path, _timeline())
    assert timeline.window(run) == (20.0 * S, 24.0 * S)
    got = {name: _read(name, run) for name in READERS}
    assert got == pytest.approx({
        "setup_chips_wait_s": 2.5,          # the worker's lease, not 8.5
        "setup_backend_s": 3.0,
        # A 0.5 + 0.25, B 1.5 + 0.5, the load without attributes 0; the
        # other process's and the window's compile are not set-up's
        "setup_trace_lower_s": 2.75,
        # covered: init 2, fit to loop 3.5, backend 3, A and B with their
        # leads 10-15 s counted ONCE (5), the load 0.5, batches 0.35
        "setup_uncovered_s": 20.0 - 14.35,
    })


def test_the_note_names_every_second_and_the_longest_gaps(tmp_path):
    run = _run(tmp_path, _timeline())
    _read("setup_uncovered_s", run)
    (note,) = run["notes"]
    assert note.startswith("set-up under the program's spans: ")
    phases, gaps = note.split("; longest uncovered: ")
    for part in (
            "runtime.init 2.00 s",
            "train.fit->train.loop 3.50 s (worker.hold_chips waited 2.50 s)",
            "train.backend_init 3.00 s",
            # A's lead 10-11 s; of B's 11.5-14 s only 13-14 s is not A's
            # compile
            "jax.compile lead 2.00 s (trace 2.00 + lowering 0.75 s over 3 "
            "spans)",
            "jax.compile 3.50 s",
            "data.next_batch 0.35 s",
            "uncovered 5.65 s: sum 20.00 s of setup_s 20.00 s"):
        assert part in phases
    at = [phases.index(p) for p in (
        "runtime.init", "train.fit->", "train.backend_init",
        "jax.compile lead", "jax.compile 3.50", "data.next_batch",
        "uncovered")]
    assert at == sorted(at)                     # in a run's order
    assert gaps == (
        "jax.compile(jit(call)) -> jax.compile(jit(train_step)) 2.00 s, "
        "before runtime.init 1.00 s, data.next_batch(1) -> the window 0.90 s")


def test_the_runs_start_is_the_windows_less_setup_s(tmp_path):
    """A shorter ``setup_s`` starts the run later: what lies before it is
    nobody's."""
    run = _run(tmp_path, _timeline(), setup_s=18.0)     # starts at 2 s
    assert _read("setup_uncovered_s", run) == pytest.approx(
        18.0 - (14.35 - 1.0))                           # init's 1-2 s cut off
    assert "before runtime.init 0.00 s" not in run["notes"][0]
    assert "sum 18.00 s of setup_s 18.00 s" in run["notes"][0]


@pytest.mark.parametrize("name", READERS)
def test_readers_say_nothing_of_a_program_without_the_spans(name, tmp_path):
    """The parent's timeline: whole, with none of the new spans or
    attributes. None, and nothing raised."""
    run = _run(tmp_path, _without_the_new(_timeline()))
    assert timeline.window(run) is not None
    assert _read(name, run) is None
    assert run["notes"] == []


def test_a_missing_attribute_counts_as_nothing(tmp_path):
    events = _timeline()
    for e in events:
        if e["name"] == "worker.hold_chips" and e["tid"] == WORKER:
            del e["args"]["waited_s"]
        if e["args"].get("fun") == "jit(call)":
            del e["args"]["lower_s"], e["args"]["lead_s"]
    run = _run(tmp_path, events)
    assert _read("setup_chips_wait_s", run) == 0.0
    assert _read("setup_trace_lower_s", run) == pytest.approx(2.25)
    # B's lead is gone: 13-14 s is uncovered now
    assert _read("setup_uncovered_s", run) == pytest.approx(6.65)


def test_a_lease_that_did_not_wait_reads_zero_not_none(tmp_path):
    events = _timeline()
    for e in events:
        if e["name"] == "worker.hold_chips" and e["tid"] == WORKER:
            e["args"]["waited_s"] = 0.0
    assert _read("setup_chips_wait_s", _run(tmp_path, events)) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_with_nothing_to_read(name, tmp_path):
    assert _read(name, _run(tmp_path / "no-file")) is None
    assert _read(name, _run(tmp_path / "no-spans", events=[])) is None


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("lost", [
    lambda e: e["name"] == "runtime.init",      # the head's cap was hit
    lambda e: e["name"] == "train.loop",        # the last report was lost
    lambda e: (e["name"] == "data.next_batch"   # a hole inside the window
               and e["args"]["index"] == 3),
], ids=["no-runtime-init", "no-train-loop", "a-batch-missing"])
def test_a_timeline_with_holes_is_read_by_none_of_them(lost, name, tmp_path):
    run = _run(tmp_path, [e for e in _timeline() if not lost(e)])
    assert timeline.window(run) is None
    assert _read(name, run) is None


@pytest.mark.parametrize("name", READERS)
def test_the_readers_are_declared_by_name(name):
    """Found by NAME, wherever later entries put them: one entry each, of
    the runtime layer, moving ``setup_s``, in the cells ``setup_compile_s``
    is read in and in its order."""
    per_layer = spec.load_benchmark()["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == name]
    (compile_s,) = [m for m in per_layer if m["name"] == "setup_compile_s"]
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "program_span", "layer": "runtime",
                     "moves": "setup_s", "workloads": compile_s["workloads"]}
    assert os.path.exists(os.path.join(
        spec.ROOT, "chipbench", "layer_metrics", f"{name}.py"))
