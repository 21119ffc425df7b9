"""CPU rehearsal of chip_smoke.py.

The script itself has no CPU mode: it runs at GPT-2 124M's full width on
a TPU, through the chip tool. Here its phase functions are driven at
`tiny` size on a cluster started with a fake ``num_tpus``, which checks
the control flow, the summary's shape and the failure path — never a
speed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def _no_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


def test_rehearsal_at_tiny_size_on_fake_chips():
    # A subprocess, so "the parent never initialized a jax backend" is a
    # fact about a fresh interpreter, not about this pytest process
    # (conftest has already touched jax).
    code = (
        "import sys, json, chip_smoke\n"
        "s = chip_smoke.smoke(chip_smoke.TINY, 'cpu', num_cpus=4, "
        "num_tpus=2, object_store_memory=128 * 1024 * 1024)\n"
        "from jax._src import xla_bridge\n"
        "s['parent_backend_initialized'] = "
        "xla_bridge.backends_are_initialized()\n"
        "print('SUMMARY ' + json.dumps(s))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("SUMMARY "))
    s = json.loads(line[len("SUMMARY "):])

    assert s["parent_backend_initialized"] is False
    assert s["size"] == "tiny" and s["chips"] == 2
    assert set(s["walls_s"]) == {"detect", "train", "kernel", "delta_rule",
                                 "afmoe_step", "serve", "shutdown", "total"}
    assert set(s["native_lanes"].values()) <= {"native", "python fallback"}
    cache = s["compile_cache"]
    assert cache["dir"] and cache["entries_after"] >= cache["entries_before"]

    t = s["train"]
    assert t["platform"] == "cpu" and t["chips_env"] == "0,1"
    assert len(t["losses"]) == 3 and t["compiles"] == 1
    assert t["mesh"] == {"fsdp": 2}
    assert t["param_devices_min"] == 2 and t["batch_devices"] == 2
    assert os.path.basename(t["checkpoint"]).startswith("checkpoint_")

    k = s["kernel"]
    assert k["chips_env"] in ("0", "1")
    assert set(k["errors"]) == {"out", "dq", "dk", "dv"}

    d = s["delta_rule"]
    assert d["shape"] == [1, 192, 2, 16] and d["prefix"] == 128
    assert set(d["errors"]) == {"o", "dq", "dk", "dv", "dg", "dbeta"}
    assert d["finite"] and d["log_decay_min"] < 0

    a = s["afmoe_step"]
    assert a["kinds"] == [[True, True], [True, True], [False, False]]
    assert a["logit_rel_d"] < 1e-4 and a["seq"] == 64        # float32 here
    assert abs(a["attn_gate_mean"] - 0.5) < 0.02
    assert 0 <= a["moe_held_share"] <= 1

    reps = s["serve"]["replicas"]
    assert len(reps) == 2
    assert {r["chips"] for r in reps.values()} == {"0", "1"}
    assert sum(r["served"] for r in reps.values()) >= 2 * 4 + 1


def test_failed_phase_names_itself_and_exits_nonzero(monkeypatch, capsys):
    def boom(size, platform):
        raise RuntimeError("kernel exploded")

    monkeypatch.setattr(chip_smoke, "train_phase",
                        lambda *a: {"platform": "cpu", "device_kind": "cpu",
                                    "device_count": 1})
    monkeypatch.setattr(chip_smoke, "kernel_phase", boom)
    served = []
    monkeypatch.setattr(chip_smoke, "serve_phase",
                        lambda *a: served.append(1) or {})
    rc = chip_smoke.report(lambda: chip_smoke.smoke(
        chip_smoke.TINY, "cpu", num_cpus=2, num_tpus=1,
        object_store_memory=64 * 1024 * 1024))
    out = capsys.readouterr()
    assert rc != 0
    assert "FAILED in phase 'kernel'" in out.err
    assert "kernel exploded" in out.err
    # Nothing is carried past a failure, and no result is printed.
    assert not served
    assert '"ok"' not in out.out
    assert not ray_tpu.is_initialized()


def test_script_refuses_a_machine_with_no_chip():
    """`python chip_smoke.py` on this chipless sandbox: non-zero, in
    seconds, saying why, and no result line."""
    t0 = time.time()
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert time.time() - t0 < 30
    assert "FAILED in phase 'detect'" in proc.stderr
    assert "no TPU chip detected" in proc.stderr
    assert '"ok"' not in proc.stdout
