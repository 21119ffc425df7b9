"""Runtime: the train worker's ``train.backend_init`` span, the first
thing inside ``train.loop``: ``import jax`` and ``jax.devices()``, i.e.
libtpu opening the leased chips. None where the timeline is not whole
(``chipbench/timeline.py``) or the program records no such span."""

from chipbench import timeline


def read(run: dict):
    if timeline.window(run) is None:
        return None
    found = timeline.named(run, "train.backend_init",
                           timeline.train_worker(run))
    return found[-1]["dur"] / 1e6 if found else None
