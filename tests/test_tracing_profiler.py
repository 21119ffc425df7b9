"""``tracing.span`` on the profiler's clock: it opens a
``jax.profiler.TraceAnnotation`` in a process that has jax loaded, and it
never imports jax itself (heads, agents, proxies and plain workers must
not start paying for it). No cluster: what a span buffers is caught at
``tracing._emit``."""

from __future__ import annotations

import subprocess
import sys

import pytest

from ray_tpu.util import tracing


@pytest.fixture
def emitted(monkeypatch):
    events: list[dict] = []
    monkeypatch.setattr(tracing, "_emit", events.append)
    return events


def test_span_imports_no_jax():
    """A fresh interpreter: ``sys.modules`` before and after a span."""
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "assert 'jax' not in sys.modules, 'importing tracing loaded jax'\n"
        "with tracing.span('plain', rows=3) as attrs:\n"
        "    attrs['more'] = 1\n"
        "tracing.record_span('after', 1.0, 2.0, {'k': 'v'})\n"
        "assert 'jax' not in sys.modules, 'span() loaded jax'\n"
        "assert 'opentelemetry' not in sys.modules\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr


def test_module_imports_neither_jax_nor_opentelemetry():
    """Not at module level and not inside a function either."""
    import ast

    with open(tracing.__file__) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert not imported & {"jax", "opentelemetry"}, imported


def test_span_opens_a_trace_annotation_when_jax_is_loaded(
        emitted, monkeypatch):
    import jax

    opened = []

    class Recording:
        def __init__(self, name, **kwargs):
            opened.append(["new", name, kwargs])

        def __enter__(self):
            opened[-1][0] = "entered"

        def __exit__(self, *exc):
            opened[-1][0] = "exited"

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    with tracing.span("data.next_batch", index=4):
        assert opened == [["entered", "data.next_batch", {"index": 4}]]
    assert opened[0][0] == "exited"
    assert [e["name"] for e in emitted] == ["data.next_batch"]


def test_real_annotation_is_a_no_op_outside_a_profiler_session(emitted):
    import jax  # noqa: F401  (the real TraceAnnotation, no session)

    with tracing.span("outer", a=1):
        with tracing.span("inner"):
            pass
    inner, outer = emitted
    assert (inner["name"], inner["parent"]) == ("inner", "outer")
    assert (outer["name"], outer["parent"]) == ("outer", None)
    assert outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]


def test_block_can_add_attributes_and_errors_are_recorded(emitted):
    with tracing.span("load", index=0) as attrs:
        attrs["rows"] = 8
    with pytest.raises(KeyError):
        with tracing.span("broken"):
            raise KeyError("k")
    load, broken = emitted
    assert load["attributes"] == {"index": 0, "rows": 8}
    assert load["failed"] is False
    assert broken["failed"] is True and "KeyError" in \
        broken["attributes"]["error"]


def test_record_span_takes_an_interval_that_is_over(emitted):
    tracing.record_span("jax.compile", 10.0, 12.5,
                        {"cache": "miss", "name": "attribute named name"})
    (ev,) = emitted
    assert (ev["event"], ev["name"], ev["start"], ev["end"]) == (
        "span", "jax.compile", 10.0, 12.5)
    assert ev["attributes"]["name"] == "attribute named name"
    assert ev["pid"] > 0 and ev["failed"] is False


def test_a_span_before_any_runtime_is_dropped_not_an_error():
    from ray_tpu._private import traceplane, worker_context

    if worker_context.try_runtime() is not None:
        pytest.skip("a runtime is attached in this process")
    before = traceplane.drain_spans()
    with tracing.span("nobody listens"):
        pass
    assert traceplane.drain_spans() == ([], 0)
    for ev in before[0]:
        traceplane.buffer_span(ev)
