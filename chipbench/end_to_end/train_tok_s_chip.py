"""Tokens of whole fetch groups finished in the window / wall at the last
group's end / chips. Clocks in the train worker; every group ends in a
host fetch of the loss; the input pipeline is running."""

from chipbench import stats


def read(run: dict):
    if run["kind"] != "train":
        return None
    return stats.whole_step_rate(run["train"]["groups"], run["cell"]["chips"])
