"""A temporary copy of the benchmark with a test-sized cell added the way
a later PR has to add one: a config file, a traffic file, a per-layer
metric reader and their ``BENCHMARK.json`` entries; no file that is
there is edited. The rehearsal tests run this cell through the real
entry points on fake chips; the "later cells are data" test is that this
works at all."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny")

CELL = {"name": "tiny-train", "config": "tiny-gpt2-train",
        "traffic": "tiny-train", "chips": 1, "why": "test-sized rehearsal"}
CONFIG = {"name": "tiny-gpt2-train", "source": "ray_tpu.models.tiny",
          "file": "chipbench/configs/tiny-gpt2-train.json", "reduced": [],
          "why": "test-sized rehearsal"}
READER = {"name": "steps_done", "unit": "steps", "better": "higher",
          "source": "host_clock", "layer": "model step",
          "moves": "train_tok_s_chip", "workloads": ["tiny-train"]}
ADDED = {  # file under tiny/ -> where a later PR would put it
    "tiny-gpt2-train.config.json": "chipbench/configs/tiny-gpt2-train.json",
    "tiny-train.traffic.json": "chipbench/traffic/tiny-train.json",
    "steps_done.reader.py": "chipbench/layer_metrics/steps_done.py",
}


def make_root(tmp: str) -> str:
    """Copy ``BENCHMARK.json`` + ``chipbench/`` to ``tmp`` and add the
    tiny cell. Returns the new root."""
    root = os.path.join(tmp, "root")
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for src, dst in ADDED.items():
        shutil.copy(os.path.join(TINY, src), os.path.join(root, dst))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(CONFIG)
    bench["workloads"].append(CELL)
    bench["per_layer"].append(READER)
    # the new cell on the metric entries the train cells already report
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "train-gpt2xl-1chip" in m.get("workloads", []):
            m["workloads"].append(CELL["name"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
