"""``moe_full_buffer``: the reader on a hand-made run record, the cells
its ``BENCHMARK.json`` entry lists, and the two tiny share cells' step
reporting the counter through ``JaxTrainer.fit``."""

from __future__ import annotations

import pytest

from chipbench import spec
from test_chipbench_kanana2 import _run_cell, root as kanana2_root  # noqa: F401
from test_chipbench_smallthinker import root as smallthinker_root  # noqa: F401

NAME = "moe_full_buffer"


@pytest.mark.parametrize("metrics,want", [
    # one of four expert layers ran the full row buffer; none did
    ({"loss": 11.0, "moe_full_buffer": 0.25}, 0.25),
    ({"loss": 11.0, "moe_full_buffer": 0.0}, 0.0),
    # a step that holds every expert, or a program from before the counter
    ({"loss": 11.0, "moe_held_share": 0.2}, None),
    ({"loss": 11.0}, None),
    # no report at all
    (None, None),
])
def test_the_reader_takes_the_last_steps_scalar(metrics, want):
    run = {"kind": "train", "cell": {"chips": 1},
           "train": {"groups": [], "tokens_per_step": 8192}}
    if metrics is not None:
        run["train"]["step_metrics"] = metrics
    read = spec.load_part("layer_metrics", NAME).read
    assert read(run) == (want if want is None else pytest.approx(want))
    assert read({"kind": "train", "train": None}) is None


def _entry(*root):
    return next(m for m in spec.load_benchmark(*root)["per_layer"]
                if m["name"] == NAME)


@pytest.mark.parametrize("cell", _entry()["workloads"])
def test_the_entry_lists_only_cells_that_hold_a_share(cell):
    """The compact buffer exists only where a rank holds some of the
    experts: a cell that holds them all reports no such counter."""
    cfg = spec.model_config(spec.load_cell(cell)["config_data"])
    assert cfg.n_experts > 0 and cfg.experts_held is not None, cell
    assert _entry()["moves"] == "train_tok_s_chip"
    assert _entry()["source"] == "program_counter"


def _counters(res: dict) -> str:
    assert res["correct"] is True, res["notes"]
    return next(n for n in res["notes"]
                if n.startswith("the last step's counters:"))


def test_the_tiny_smallthinker_cell_reports_the_counter(smallthinker_root):
    assert "tiny-smallthinker" in _entry(smallthinker_root)["workloads"]
    counters = _counters(_run_cell(smallthinker_root, "tiny-smallthinker"))
    for name in ("moe_held_share", "moe_load_max", NAME):
        assert name in counters


def test_the_tiny_kanana2_cell_reports_the_counter(kanana2_root):
    assert "tiny-kanana2" in _entry(kanana2_root)["workloads"]
    assert NAME in _counters(_run_cell(kanana2_root, "tiny-kanana2"))
