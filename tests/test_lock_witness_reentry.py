"""The lock witness under the garbage collector: recording an edge
allocates under the witness's own lock, so the collector can run a
``__del__`` that takes a witnessed lock (``ObjectRef.__del__`` does) and
re-enter the witness on the same thread. That once hung a whole test run
(every head thread behind the one that waited for itself)."""

from __future__ import annotations

import threading

from ray_tpu._private import lockwitness


def test_an_edge_met_while_recording_an_edge_is_not_waited_for(monkeypatch):
    inner: list = []

    def format_stack_that_meets_a_finalizer(*args, **kwargs):
        # what a __del__ run by the collector here would do
        inner.append(lockwitness._record_edge(
            "site-c", "c.py:1", "site-d", "d.py:1"))
        return ["frame"]

    monkeypatch.setattr(lockwitness.traceback, "format_stack",
                        format_stack_that_meets_a_finalizer)
    monkeypatch.setattr(lockwitness, "_edges", {})
    done = threading.Thread(
        target=lockwitness._record_edge,
        args=("site-a", "a.py:1", "site-b", "b.py:1"), daemon=True)
    done.start()
    done.join(10)
    assert not done.is_alive(), "the witness waits for its own lock"
    assert inner == [None]
    assert set(lockwitness._edges) == {("site-a", "site-b")}
    # the skipped edge is recorded at its next occurrence
    lockwitness._record_edge("site-c", "c.py:1", "site-d", "d.py:1")
    assert ("site-c", "site-d") in lockwitness._edges
