"""Device: time a collective is in flight with no other instruction
running on that chip / the traced window, averaged over the chips."""

from chipbench import xplane


def read(run: dict):
    t = run.get("trace")
    window = (run.get("traced") or {}).get("window_s")
    if t is None or not window or len(t.devices) < 2:
        return None
    return 100.0 * xplane.collective_exposed_s(t) / window
