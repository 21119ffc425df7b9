"""Runtime: what the jits of set-up cost in Python before the compiler
was asked: the sum of ``trace_s`` + ``lower_s`` (tracing to a jaxpr,
lowering to an MLIR module; ``_private/compile_cache.py``) over the train
worker's ``jax.compile`` spans that end before the measured window
starts. Disjoint from ``setup_compile_s``, which sums the same spans' own
durations (the backend's): the two add. None where the timeline is not
whole (``chipbench/timeline.py``) or no span carries the attributes (a
program from before they were recorded); a span without them counts 0."""

from chipbench import timeline


def read(run: dict):
    w = timeline.window(run)
    if w is None:
        return None
    args = [e.get("args") or {} for e in timeline.named(
        run, "jax.compile", timeline.train_worker(run))
        if timeline.end(e) < w[0]]
    if not any("trace_s" in a for a in args):
        return None
    return float(sum((a.get("trace_s") or 0.0) + (a.get("lower_s") or 0.0)
                     for a in args))
