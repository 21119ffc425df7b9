"""Runtime: sum of the train worker's ``jax.compile`` spans that end
before the measured window starts: what compiling, or loading from the
compile cache, costs a job's set-up (``chipbench/timeline.py``)."""

from chipbench import timeline


def read(run: dict):
    w = timeline.window(run)
    if w is None:
        return None
    found = timeline.named(run, "jax.compile", timeline.train_worker(run))
    return sum(e["dur"] for e in found if timeline.end(e) < w[0]) / 1e6
