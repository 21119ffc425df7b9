"""Device: 1 - (union of the intervals in which an instruction ran) /
the traced window, averaged over the chips. A layer scan's ``while`` is
no instruction (``chipbench/xplane.py``): a stall inside it is idle."""

from chipbench import xplane


def read(run: dict):
    t = run.get("trace")
    window = (run.get("traced") or {}).get("window_s")
    if t is None or not window:
        return None
    return 100.0 * max(0.0, 1.0 - xplane.busy_s(t) / window)
