"""Shared by the ``program_counter`` readers: one scalar of the LAST
step's metrics dict, which the train driver fetches once after the window
has closed and reports under ``step_metrics``. None where the run has no
report or the step reports no such counter (never a 0)."""


def scalar(run: dict, name: str):
    return ((run.get("train") or {}).get("step_metrics") or {}).get(name)
