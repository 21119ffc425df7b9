"""What a cell is made of, found by name.

``BENCHMARK.json`` names cells, configurations and metrics; everything
that belongs to one of them is a file in a scanned directory of this
package, found by that name:

  configs/<config>.json        sizes, source, cuts, ``arch``, factory + kwargs
  traffic/<traffic>.json       parameters; its ``kind`` picks the driver
  drivers/<kind>.py            run(ctx) -> the run's raw record
  end_to_end/<metric>.py       read(run) -> float | None
  layer_metrics/<metric>.py    read(run) -> float | None
  reference/<arch>.py          forward(params, tokens, cfg): the plain
                               float32 logits; optionally loss(params,
                               rows, cfg), the whole training loss
  flops/<arch>.py              model FLOPs a token, from the sizes
  sizes/<arch>.py              check(data, cfg): the config file states
                               the sizes its factory runs
  peaks/<device_kind>.json     the chip's published peaks

There is no registry: a later PR adds files and ``BENCHMARK.json``
entries and edits nothing here. Nothing in this module touches jax.

A configuration of a NEW architecture is added the same way. Its config
file names an ``arch`` no file knows yet, and the PR brings the three
files of that name: ``reference/<arch>.py`` (with ``loss`` where the
recipe's training loss is more than next-token cross entropy: router
losses, a z loss), ``flops/<arch>.py`` (``matmul_params``, ``n_params``,
``train_flops_per_token``) and ``sizes/<arch>.py``. To ``BENCHMARK.json``
it appends the entry under ``configs``, its cell under ``workloads``, and
the cell's name to the ``workloads`` list of every ``end_to_end`` and
``per_layer`` entry the cell reports (a train cell: those that list
``train-gpt2xl-1chip``). The tests pick the rest up by themselves: the
spec tests run over every file in ``configs/``, and the rehearsal compile
(``tests/chipbench/test_chipbench_rehearsal.py``) over every entry of
``workloads``. ``tests/chipbench/_tinycells.py`` does exactly this, for
an architecture called ``tinymoe``, in a temporary copy.

What a train cell's ``correct`` holds the program to is
``reference/_common.py`` ``LIMITS``. A configuration whose sound program
and controls do not fit ``logit_rel_d``'s (they move with width and
depth) states its own under ``agreement_limits`` in its file: the limit
WITH the two readings on the chip that place it (the sound program's
worst, the nearest control's: ``python -m chipbench.reference.compare
--control ...``) and the why. ``limits_for`` refuses a limit that those
readings do not place by the one rule every limit obeys, so a file
chooses its readings' cell, never its pass mark; the table they come
from goes to PERF.md section 4.
"""

from __future__ import annotations

import importlib
import json
import os
import re

PACKAGE_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PACKAGE_DIR)


class SpecError(Exception):
    """The benchmark's own files do not fit together."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                    f"{[e['name'] for e in entries]}")


def load_json(*parts: str, root: str = ROOT) -> dict:
    path = os.path.join(root, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no file {os.path.relpath(path, root)}") from None


def load_cell(workload: str, root: str = ROOT) -> dict:
    """One cell with its configuration and traffic files read in."""
    bench = load_benchmark(root)
    cell = dict(_by_name(bench["workloads"], workload, "workload"))
    entry = _by_name(bench["configs"], cell["config"], "config")
    cell["config_entry"] = entry
    cell["config_data"] = load_json(entry["file"], root=root)
    cell["traffic_data"] = load_json(
        "chipbench", "traffic", cell["traffic"] + ".json", root=root)
    return cell


def metrics_of(workload: str, section: str, root: str = ROOT) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports:
    those with no ``workloads`` key, or with the cell in it."""
    return [m for m in load_benchmark(root)[section]
            if "workloads" not in m or workload in m["workloads"]]


def load_part(directory: str, name: str):
    """The module ``chipbench/<directory>/<name>.py``. A name is made of
    letters, digits, ``_``, ``.`` and ``-``; only ``_`` names import, so
    ``-`` and ``.`` in a name map to ``_`` in the file's name."""
    mod = re.sub(r"[^0-9A-Za-z_]", "_", name)
    try:
        return importlib.import_module(f"chipbench.{directory}.{mod}")
    except ModuleNotFoundError as e:
        if e.name != f"chipbench.{directory}.{mod}":
            raise
        raise SpecError(f"no file chipbench/{directory}/{mod}.py "
                        f"(for {name!r})") from None


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """Published peaks of one chip, keyed by jax's ``device_kind``. A
    kind with no file is an error, never a default."""
    fname = re.sub(r"[^0-9A-Za-z_.-]", "_", device_kind) + ".json"
    peaks = load_json("chipbench", "peaks", fname, root=root)
    if peaks["device_kind"] != device_kind:
        raise SpecError(f"{fname} describes {peaks['device_kind']!r}, "
                        f"not {device_kind!r}")
    return peaks


def model_config(config_data: dict, **overrides):
    """The program's model configuration for a config file: a factory in
    ``ray_tpu.models`` named by string, plus kwargs."""
    from ray_tpu import models

    factory = getattr(models, config_data["factory"], None)
    if factory is None:
        raise SpecError(f"ray_tpu.models has no factory "
                        f"{config_data['factory']!r}")
    return factory(**{**config_data.get("factory_kwargs", {}), **overrides})


def cache_dir(root: str = ROOT) -> str:
    """Where the benchmark keeps what later runs of a checkout reuse
    (first-run losses, the last run's record, traces): a fixed path
    inside the checkout, beside the program's ``.jax_cache``."""
    path = os.path.join(root, ".chipbench_cache")
    os.makedirs(path, exist_ok=True)
    return path
