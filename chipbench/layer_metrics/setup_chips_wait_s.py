"""Runtime: how long the train worker's chip lease waited for the last
holder of its chips to let go: ``waited_s`` of its ``worker.hold_chips``
span (``Worker._hold_chips``; 0.0 where the device nodes opened at once).
The wait lies INSIDE ``setup_runtime_s`` (between ``train.fit``'s start
and ``train.loop``'s), not beside it. None where the timeline is not whole
(``chipbench/timeline.py``) or the program records no such span."""

from chipbench import timeline


def read(run: dict):
    if timeline.window(run) is None:
        return None
    held = timeline.named(run, "worker.hold_chips", timeline.train_worker(run))
    if not held:
        return None
    return float((held[-1].get("args") or {}).get("waited_s") or 0.0)
