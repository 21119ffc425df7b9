"""``_jax_batches`` names the time a train loop spends asking for its next
batch: one ``data.next_batch`` span a batch, with the wait for the numpy
batch and the transfer to the device as children."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from ray_tpu.data import dataset
from ray_tpu.util import tracing


@pytest.fixture
def spans(monkeypatch):
    events: list[dict] = []
    monkeypatch.setattr(tracing, "_emit", events.append)
    return events


def _numpy_batches(n, rows=4, width=6):
    for i in range(n):
        yield {"tokens": np.full((rows, width), i, np.int32),
               "text": np.array(["a"] * rows, dtype=object)}


def test_each_batch_is_a_span_with_both_children(spans):
    out = list(dataset._jax_batches(_numpy_batches(3), None, None))
    assert [int(b["tokens"][0, 0]) for b in out] == [0, 1, 2]
    assert all(isinstance(b["tokens"], jax.Array) for b in out)
    assert out[0]["text"].dtype == object        # left on the host

    batches = [e for e in spans if e["name"] == "data.next_batch"]
    # three batches, then the call that found the iterator exhausted
    assert [e["attributes"]["index"] for e in batches] == [0, 1, 2, 3]
    assert [e["attributes"]["rows"] for e in batches] == [4, 4, 4, 0]
    waits = [e for e in spans if e["name"] == "data.block_wait"]
    puts = [e for e in spans if e["name"] == "data.to_device"]
    assert len(waits) == 4 and len(puts) == 3
    assert {e["parent"] for e in waits + puts} == {"data.next_batch"}
    assert [e["attributes"]["bytes"] for e in puts] == [4 * 6 * 4] * 3
    for b, w, p in zip(batches, waits, puts):
        assert b["start"] <= w["start"] <= w["end"] <= p["start"] \
            <= p["end"] <= b["end"]


def test_the_consumers_time_is_in_no_span(spans):
    """The span closes before the batch is handed over: what the loop
    does with a batch is not input time."""
    it = dataset._jax_batches(_numpy_batches(2), None, {"tokens": "float32"})
    first = next(it)
    assert first["tokens"].dtype == np.float32
    # (the cast may compile: its ``jax.compile`` span is not input's)
    data = lambda: [e["name"] for e in spans if e["name"].startswith("data.")]
    assert data() == ["data.block_wait", "data.to_device", "data.next_batch"]
    assert tracing._local.span_name is None
    it.close()
    assert len(data()) == 3
