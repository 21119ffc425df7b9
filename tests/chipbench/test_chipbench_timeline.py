"""``chipbench/timeline.py`` and the five readers of the program's own
spans, on a hand-written ``timeline.json`` whose answers are computed by
hand; every reader returns None when the run left no timeline."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import spec, timeline

READERS = ("input_block_wait_ms", "input_to_device_ms", "compiles_in_window",
           "setup_runtime_s", "setup_compile_s")
DRIVER, WORKER, OTHER = 100, 200, 300
S = 1e6     # the timeline counts microseconds


def _span(name, start_s, dur_s, tid, **args):
    return {"cat": "span", "ph": "X", "name": name, "ts": start_s * S,
            "dur": dur_s * S, "pid": 0, "tid": tid, "args": args}


def _timeline():
    """Driver: init 0-2 s, fit from 3 s. Worker: loop from 10 s; two
    warm-up batches; the window is 20-24 s (``window_s`` 4) with batches
    2, 3, 4; batch 5 starts after it."""
    ev = [
        _span("runtime.init", 0.0, 2.0, DRIVER, head="started"),
        _span("train.fit", 3.0, 30.0, DRIVER),
        _span("train.worker.setup", 9.0, 0.5, WORKER, rank=0),
        _span("train.loop", 10.0, 22.0, WORKER, rank=0),
        _span("jax.compile", 11.0, 5.0, WORKER, cache="miss"),
        _span("jax.compile", 17.0, 0.5, WORKER, cache="hit"),
        _span("jax.compile", 12.0, 9.0, OTHER, cache="miss"),   # not ours
        _span("jax.compile", 21.0, 0.25, WORKER, cache="miss"),  # in window
        {"cat": "task", "ph": "X", "name": "data.block_wait", "ts": 20 * S,
         "dur": 9 * S, "pid": 0, "tid": WORKER, "args": {}},     # no span
    ]
    for index, start, wait_ms, put_ms in (
            (0, 18.0, 500, 40), (1, 19.0, 300, 30), (2, 20.0, 1, 4),
            (3, 21.5, 3, 2), (4, 23.0, 2, 6), (5, 25.0, 900, 90)):
        ev += [
            _span("data.next_batch", start, (wait_ms + put_ms) / 1e3, WORKER,
                  index=index, rows=8),
            _span("data.block_wait", start, wait_ms / 1e3, WORKER),
            _span("data.to_device", start + wait_ms / 1e3, put_ms / 1e3,
                  WORKER, bytes=64),
        ]
    return ev


def _run(tmp_path, events=None, window_s=4.0) -> dict:
    run = {"root": str(tmp_path), "train": {"window_s": window_s},
           "cell": {"name": "hand", "traffic_data": {"warmup_steps": 2}}}
    if events is not None:
        path = timeline.path_of(run)
        os.makedirs(os.path.dirname(path))
        with open(path, "w") as f:
            json.dump(events, f)
    return run


def _read(name: str, run: dict):
    return spec.load_part("layer_metrics", name).read(run)


def test_window_starts_at_the_first_measured_batch(tmp_path):
    run = _run(tmp_path, _timeline())
    assert timeline.path_of(run).endswith(
        ".chipbench_cache/train_runs/chipbench-hand/timeline.json")
    assert timeline.train_worker(run) == WORKER
    assert timeline.window(run) == (20.0 * S, 24.0 * S)
    assert [e["args"].get("index") for e in timeline.in_window(
        run, "data.next_batch")] == [2, 3, 4]


def test_readers_on_the_hand_written_timeline(tmp_path):
    run = _run(tmp_path, _timeline())
    got = {name: _read(name, run) for name in READERS}
    assert got == pytest.approx({
        "input_block_wait_ms": 2.0,     # median of 1, 3, 2
        "input_to_device_ms": 4.0,      # median of 4, 2, 6
        "compiles_in_window": 1.0,      # the worker's, ending at 21.25 s
        "setup_runtime_s": 2.0 + (10.0 - 3.0),
        "setup_compile_s": 5.5,         # the worker's two before 20 s
    })


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_with_nothing_to_read(name, tmp_path):
    assert _read(name, _run(tmp_path / "no-file")) is None
    assert _read(name, _run(tmp_path / "no-spans", events=[])) is None
    half = tmp_path / "unreadable"
    run = _run(half, events=[])
    with open(timeline.path_of(run), "w") as f:
        f.write("[{")
    assert _read(name, run) is None


def test_no_window_without_the_first_measured_batch(tmp_path):
    events = [e for e in _timeline() if e["name"] != "data.next_batch"]
    run = _run(tmp_path, events)
    assert timeline.window(run) is None
    assert _read("compiles_in_window", run) is None
    assert _read("setup_compile_s", run) is None
    assert _read("setup_runtime_s", run) == pytest.approx(9.0)


@pytest.mark.parametrize("lost", [
    lambda e: e["name"] == "runtime.init",      # the head's cap was hit
    lambda e: e["name"] == "train.loop",        # the last report was lost
    lambda e: (e["name"] == "data.next_batch"   # a hole inside the window
               and e["args"]["index"] == 3),
])
def test_a_timeline_with_holes_has_no_window(lost, tmp_path):
    """Spans can be lost on their way; a count over what is left would
    prove nothing, so the window's readers say nothing."""
    run = _run(tmp_path, [e for e in _timeline() if not lost(e)])
    assert timeline.window(run) is None
    for name in ("compiles_in_window", "setup_compile_s",
                 "input_block_wait_ms", "input_to_device_ms"):
        assert _read(name, run) is None
