"""Input (data/, train/session.py): share of the window the loop spent
inside ``next(batches)``, by the loop's own clock."""


def read(run: dict):
    if run["kind"] != "train" or not run["train"]["window_s"]:
        return None
    return 100.0 * run["train"]["input_wait_s"] / run["train"]["window_s"]
