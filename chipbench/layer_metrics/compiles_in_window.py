"""Model step: ``jax.compile`` spans of the train worker that end inside the
measured window: every compile and every load from the compile cache
(``cache`` ``miss`` and ``hit``), those under a second included, which the
count of new cache files misses. A count of spans, and spans can be lost
on their way: None unless the timeline shows itself whole over the window
(``chipbench/timeline.py``, WHOLE OR NOTHING), so a 0 means none."""

from chipbench import timeline


def read(run: dict):
    found = timeline.in_window(run, "jax.compile")
    return None if found is None else float(len(found))
