"""Input (data/): median of the ``data.to_device`` spans of the window's
batches (``jnp.asarray`` / ``astype`` / ``jax.device_put`` with the
sharding), by the train worker's own spans (``chipbench/timeline.py``)."""

from chipbench import stats, timeline


def read(run: dict):
    found = timeline.in_window(run, "data.to_device")
    m = stats.median([e["dur"] for e in found or []])
    return None if m is None else m / 1e3
