"""The train driver through the real entry point (``JaxTrainer.fit``) at
test size on fake chips, through the Python API only. The results say
``platform: cpu``: they check control flow, counts and the shape of the
result line, never a speed. The cell they run exists only as files and
entries ADDED to a temporary copy of the benchmark, which is the proof
that a later PR can add a cell without editing a file that is there.

Each run is a subprocess: "the parent never initialises a jax backend"
is then a fact about a fresh interpreter.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import _tinycells

REHEARSAL = "dict(num_cpus=4, num_tpus=2, object_store_memory=128 * 1024 * 1024)"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _tinycells.make_root(str(tmp_path_factory.mktemp("chipbench")))


def _run(root: str, cell: str, trace: int, seconds: float, seed: int = 5):
    code = (
        "import json, sys\n"
        "from chipbench import run\n"
        f"res = run.run_cell({cell!r}, seed={seed}, seconds={seconds}, "
        f"trace=bool({trace}), root={root!r}, rehearsal={REHEARSAL})\n"
        "from jax._src import xla_bridge\n"
        "res['parent_backend_initialized'] = "
        "xla_bridge.backends_are_initialized()\n"
        "print('RESULT ' + json.dumps(res))\n")
    env = dict(os.environ, PYTHONPATH=_tinycells.REPO)
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def _check_line(res: dict, want_metrics: set):
    assert res["parent_backend_initialized"] is False
    assert res["correct"] is True, res["notes"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"      # never a device metric
    assert set(res["metrics"]) >= want_metrics, res["metrics"]
    for m in res["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]


def test_later_cells_are_data(root):
    """One config, one traffic file, one per-layer reader and their
    BENCHMARK.json entries were added to a copy; no file that was there
    differs; the harness lists the new cell with its metrics."""
    from chipbench import spec

    added = {os.path.normpath(p) for p in _tinycells.ADDED.values()}
    for d, _dirs, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), root)
            if rel in added or "__pycache__" in rel or ".chipbench" in rel:
                continue
            with open(os.path.join(root, rel), "rb") as a, \
                    open(os.path.join(_tinycells.REPO, rel), "rb") as b:
                assert a.read() == b.read(), rel
    cell = spec.load_cell("tiny-train", root)
    assert cell["config_data"]["factory"] == "tiny"
    assert cell["traffic_data"]["kind"] == "train_job"
    assert [m["name"] for m in spec.metrics_of(
        "tiny-train", "end_to_end", root)] == ["train_tok_s_chip", "setup_s"]
    assert "steps_done" in [m["name"] for m in spec.metrics_of(
        "tiny-train", "per_layer", root)]
    # and the real cells are still there, untouched, without the new metric
    assert spec.load_cell("train-mistral-fsdp4", root)["chips"] == 4
    assert "steps_done" not in [m["name"] for m in spec.metrics_of(
        "train-gpt2xl-1chip", "per_layer", root)]


def test_added_cell_rehearses_traced_with_its_own_reader(root):
    res = _run(root, "tiny-train", 1, 3.0, seed=7)
    _check_line(res, {"steps_done", "train_step_ms", "input_wait_share"})
    assert res["metrics"]["steps_done"]["value"] == res["attempted"]
    assert res["metrics"]["steps_done"]["unit"] == "steps"
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "train_tok_s_chip" not in res["metrics"]   # traced: per-layer only
    assert "mfu" not in res["metrics"]                # no peaks off the chip


def test_train_cell_rehearses_and_losses_repeat(root):
    first = _run(root, "tiny-train", 0, 3.0, seed=9)
    _check_line(first, {"train_tok_s_chip", "setup_s"})
    again = _run(root, "tiny-train", 0, 3.0, seed=9)
    _check_line(again, {"train_tok_s_chip", "setup_s"})
    assert any("compared with this seed's first run" in n
               for n in again["notes"])


def test_no_chip_no_result():
    """The command has no CPU mode: here, with no chip, it exits non-zero
    and prints no result line."""
    env = dict(os.environ, PYTHONPATH=_tinycells.REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "train-gpt2xl-1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=_tinycells.REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert "needs 1 chip" in proc.stderr
