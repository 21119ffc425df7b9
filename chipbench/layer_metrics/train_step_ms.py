"""Model step: median over fetch groups of group wall / steps, by the
train worker's clock (each group ends in a host fetch)."""

from chipbench import stats


def read(run: dict):
    if run["kind"] != "train":
        return None
    t = run["train"]
    per = [s / g["steps"] for s, g in zip(t["group_s"], t["groups"])]
    m = stats.median(per)
    return None if m is None else 1e3 * m
