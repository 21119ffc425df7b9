"""Runtime (_private/, train/): the ``runtime.init`` span (head up, driver
attached) + the time from the start of ``train.fit`` to the start of
``train.loop`` (run state, worker group, the worker process reaching its
chips, backend set-up), from the run's timeline
(``chipbench/timeline.py``)."""

from chipbench import timeline


def read(run: dict):
    init = timeline.named(run, "runtime.init")
    fit = timeline.named(run, "train.fit")
    loop = timeline.named(run, "train.loop")
    if not (init and fit and loop):
        return None
    return (init[-1]["dur"] + loop[-1]["ts"] - fit[-1]["ts"]) / 1e6
