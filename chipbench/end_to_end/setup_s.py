"""Process start to the first measured step: chips, trainer, weights,
the reference, compile or cache load, warm-up steps."""


def read(run: dict):
    return run["setup_s"]
