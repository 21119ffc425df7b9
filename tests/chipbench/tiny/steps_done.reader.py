"""A per-layer metric a later PR might add: one file with ``read(run)``,
copied by the tests into ``chipbench/layer_metrics/`` of a temporary copy
of the benchmark. Whole steps finished in the window."""


def read(run: dict):
    if run["kind"] != "train":
        return None
    return float(sum(g["steps"] for g in run["train"]["groups"]))
