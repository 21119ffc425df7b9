"""Input (data/): median of the ``data.block_wait`` spans of the window's
batches: the time inside ``next()`` on the numpy batch iterator (blocks
from the object store, ``map_batches`` tasks, rebatching), by the train
worker's own spans (``chipbench/timeline.py``)."""

from chipbench import stats, timeline


def read(run: dict):
    found = timeline.in_window(run, "data.block_wait")
    m = stats.median([e["dur"] for e in found or []])
    return None if m is None else m / 1e3
