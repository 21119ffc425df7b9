"""What the ``smallthinker`` configuration brings to the benchmark: its
three per-layer readers on a hand-written trace whose answers are computed
by hand (and with nothing to read), its FLOP functions against the
program's own parameter count and a count of visible pairs made from the
mask, its size check, and its cell at test size through the real entry
point (``JaxTrainer.fit`` on fake chips), added to a temporary copy of the
benchmark the way a later PR adds a cell. CPU only; the cell itself is
rehearsed at its real size by ``test_chipbench_rehearsal.py`` and held to
the contract by ``test_chipbench_spec.py``, which pick it up by name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import _tinycells
from chipbench import spec, xplane

CELL = "train-smallthinker-ep4share"
ATTN_READERS = ("step_attn_window_ms", "step_attn_full_ms",
                "attn_kernel_peak_share")
FWD = "jit(train_step)/jvp(layers)/while/body/closed_call"
BWD = "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint"
# One device, two runs of the train step in 20 us. Leaves (us):
#   fwd.1    0-3    attn_full kernel forward    fwd.2   3-5   attn_window kernel forward
#   fwd.3    5-7    attn_window kernel recompute
#   dq.4     7-11   attn_full kernel backward   dkv.5  11-14  attn_window kernel backward
#   fusion.6 14-16  attn_window projections (no kernel)
#   fusion.7 16-17  attn_full projections       gmm.8  17-19  moe_experts (a kernel, not attention's)
#   fusion.9 19-20  attn of a model with no pattern: no sub-scope
# window 2+2+3+2 = 9 us, full 3+4+1 = 8 us, kernels 3+2+2+4+3 = 14 us, two runs.
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 4000000 }
    events { metadata_id: 5 offset_ps: 11000000 duration_ps: 3000000 }
    events { metadata_id: 6 offset_ps: 14000000 duration_ps: 2000000 }
    events { metadata_id: 7 offset_ps: 16000000 duration_ps: 1000000 }
    events { metadata_id: 8 offset_ps: 17000000 duration_ps: 2000000 }
    events { metadata_id: 9 offset_ps: 19000000 duration_ps: 1000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 10 offset_ps: 10000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fwd.1 = bf16[8]{0} custom-call(bf16[8]{0} %a)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_full/jvp()/pallas_call:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fwd.2 = bf16[8]{0} custom-call(bf16[8]{0} %b)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_window/jvp()/pallas_call:" } } }
  event_metadata { key: 3 value { id: 3 name: "%fwd.3 = bf16[8]{0} custom-call(bf16[8]{0} %c)"
    stats { metadata_id: 1 str_value: "BWD/rematted_computation/attn/attn_window/jvp()/pallas_call:" } } }
  event_metadata { key: 4 value { id: 4 name: "%dq.4 = bf16[8]{0} custom-call(bf16[8]{0} %d)"
    stats { metadata_id: 1 str_value: "BWD/attn/attn_full/transpose(jvp())/pallas_call:" } } }
  event_metadata { key: 5 value { id: 5 name: "%dkv.5 = bf16[8]{0} custom-call(bf16[8]{0} %e)"
    stats { metadata_id: 1 str_value: "BWD/attn/attn_window/transpose(jvp())/pallas_call:" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = bf16[8]{0} fusion(bf16[8]{0} %f)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_window/dot_general:" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %g)"
    stats { metadata_id: 1 str_value: "jit(train_step)/transpose(jvp())/reshape;BWD/attn/attn_full/dot_general:" } } }
  event_metadata { key: 8 value { id: 8 name: "%gmm.8 = bf16[8]{0} custom-call(bf16[8]{0} %h)"
    stats { metadata_id: 1 str_value: "FWD/moe/moe_experts/jit(gmm)/pallas_call:" } } }
  event_metadata { key: 9 value { id: 9 name: "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %i)"
    stats { metadata_id: 1 str_value: "FWD/attn/dot_general:" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_train_step(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
'''.replace("FWD", FWD).replace("BWD", BWD)


def _write(tmp_path, text: str) -> str:
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True, exist_ok=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def _run(trace_dir: str) -> dict:
    cell = spec.load_cell(CELL)
    return {"trace": xplane.load(xplane.find_xplane(trace_dir)),
            "trace_dir": trace_dir, "notes": [], "cell": cell,
            "train": {"tokens_per_step": 16384},
            "peaks": spec.load_peaks("TPU v5 lite")}


def _read(name: str, run: dict):
    return spec.load_part("layer_metrics", name).read(run)


def test_attention_readers_on_the_hand_trace(tmp_path):
    run = _run(_write(tmp_path, HAND))
    assert _read("step_attn_window_ms", run) == pytest.approx(4.5e-3)
    assert _read("step_attn_full_ms", run) == pytest.approx(4.0e-3)
    # the benchmark's own reader gives both to ``attn``, plus fusion.9
    assert _read("step_attn_ms", run) == pytest.approx(9.0e-3)
    # seven matmuls over the visible pairs of one global and three windowed
    # layers, 28 heads of 128, over 7 us of kernels a step and the peak
    pairs = 16384 * 16385 // 2 + 3 * (4096 * 4097 // 2 + 12288 * 4096)
    assert _read("attn_kernel_peak_share", run) == pytest.approx(
        100 * 7 * 2 * 28 * 128 * pairs / 7.0e-6 / 197e12)
    assert any(n.startswith("attn scopes:") and "attn_window kernels" in n
               for n in run["notes"])
    run["peaks"] = None                      # a CPU rehearsal: no share
    assert _read("attn_kernel_peak_share", run) is None


@pytest.mark.parametrize("name", ATTN_READERS)
def test_attention_readers_return_none_with_nothing_to_read(name, tmp_path):
    assert _read(name, {"trace": None, "trace_dir": None, "notes": []}) is None
    # a program (or a model) whose attention has no sub-scopes
    plain = HAND.replace("/attn_full/", "/").replace("/attn_window/", "/")
    run = _run(_write(tmp_path, plain))
    assert _read(name, run) is None
    assert _read("step_attn_ms", run) == pytest.approx(9.0e-3)
    assert any("neither of them on any instruction" in n
               for n in run["notes"])


def test_smallthinker_flop_functions_count_the_programs_parameters():
    from chipbench.flops import smallthinker as flops

    data = spec.load_json("chipbench", "configs",
                          "smallthinker-21b-a3b-ep4.json")
    cfg = spec.model_config(data)
    assert flops.n_params(cfg) == cfg.num_params() == 656_529_920
    shapes = cfg.shapes()["layers"]
    per_layer_matmul = sum(
        int(np.prod(s.shape[1:])) for s in shapes["attn"].values()) + int(
        np.prod(shapes["router"]["w"].shape[1:]))
    one_expert = sum(int(np.prod(s.shape[2:])) for s in shapes["mlp"].values())
    assert one_expert == 3 * 2560 * 768 and flops.held_share(cfg) == 0.25
    assert flops.matmul_params(cfg) == pytest.approx(
        4 * (per_layer_matmul + 6 * 0.25 * one_expert) + 2560 * 37984)
    assert flops.experts_train_flops_per_token(cfg) == pytest.approx(
        6 * 4 * 1.5 * one_expert)
    # visible pairs, counted from the mask itself
    for t, window in ((64, 16), (64, None), (64, 64), (64, 100), (33, 1)):
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        mask = (j <= i) if window is None else (j <= i) & (i - j < window)
        assert flops.visible_pairs(t, window) == int(mask.sum()), (t, window)
    assert flops.layer_windows(cfg) == [None, 4096, 4096, 4096]
    pairs = flops.visible_pairs(16384, None) + 3 * flops.visible_pairs(
        16384, 4096)
    assert flops.attention_flops_per_token(cfg, 16384) == pytest.approx(
        4 * 28 * 128 * pairs / 16384)
    assert flops.train_flops_per_token(cfg, 16384) == pytest.approx(
        6 * flops.matmul_params(cfg)
        + 3 * flops.attention_flops_per_token(cfg, 16384))
    # about 35 TFLOP of model work a step of 16,384 tokens
    assert 34e12 < flops.train_flops_per_token(cfg, 16384) * 16384 < 36e12
    assert flops.attention_kernel_flops_per_step(cfg, 16384, 1) == \
        pytest.approx(7 * 2 * 28 * 128 * pairs)
    # compute is the kernels' bound on this chip (peak FLOP per peak byte)
    intensity = (flops.attention_kernel_flops_per_step(cfg, 16384, 1)
                 / flops.attention_kernel_bytes_per_step(cfg, 16384, 1))
    assert intensity > 197e12 / 819e9


@pytest.mark.parametrize("changes,named", [
    (dict(sliding_window=2048), "sliding_window_size: the file states 4096"),
    (dict(experts_held=(0, 2)), "moe_num_primary_experts: the file states 16"),
    (dict(expert_activation="silu"), "expert_activation: the file states 'relu'"),
    (dict(router_input="mlp_norm"), "router_input: the file states 'attn_norm'"),
    (dict(layer_pattern=((False, True), (True, True), (True, True),
                         (True, True))), r"rope_layout: the file states \[0, 1, 1, 1\]"),
    (dict(d_head=64), "head_dim: the file states 128"),
    (dict(norm_eps=1e-5), "rms_norm_eps: the file states 1e-06"),
    (dict(expert_top_k=8), "moe_num_active_primary_experts: the file states 6"),
])
def test_smallthinker_size_check_names_what_the_factory_runs_differently(
        changes, named):
    data = spec.load_json("chipbench", "configs",
                          "smallthinker-21b-a3b-ep4.json")
    check = spec.load_part("sizes", "smallthinker").check
    check(data, spec.model_config(data))
    with pytest.raises(spec.SpecError, match=named):
        check(data, spec.model_config(data, **changes))


def test_the_config_file_states_the_catalogs_numbers_and_its_cuts():
    data = spec.load_json("chipbench", "configs",
                          "smallthinker-21b-a3b-ep4.json")
    assert data["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                               "vocab_size"]
    assert data["published"] == {"num_hidden_layers": 52,
                                 "moe_num_primary_experts": 64,
                                 "vocab_size": 151936}
    # no width differs from the source
    assert (data["hidden_size"], data["head_dim"], data["moe_ffn_hidden_size"],
            data["num_attention_heads"], data["num_key_value_heads"],
            data["moe_num_active_primary_experts"],
            data["sliding_window_size"], data["rope_theta"],
            data["max_position_embeddings"]) == (
        2560, 128, 768, 28, 4, 6, 4096, 1500000, 16384)
    assert len(data["rope_layout"]) == len(data["sliding_window_layout"]) == 52
    assert data["vocab_size"] * 4 == 151936
    assert "four chips share each layer" in data["deployment"]
    traffic = spec.load_cell(CELL)["traffic_data"]
    assert (traffic["seq_len"], traffic["rows_per_chip"],
            traffic["fetch_every"]) == (16384, 1, 4)


# -- the cell at test size through JaxTrainer.fit ----------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with ``tiny-smallthinker`` added: a config
    file of the ``smallthinker`` arch at test size and the tiny traffic
    file; the arch's own reference, FLOP count, size check and readers are
    the repository's."""
    root = os.path.join(str(tmp_path_factory.mktemp("smallthinker")), "root")
    shutil.copytree(os.path.join(_tinycells.REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(_tinycells.TINY, "tiny-smallthinker.config.json"),
                os.path.join(root, "chipbench/configs/tiny-smallthinker.json"))
    shutil.copy(os.path.join(_tinycells.TINY, "tiny-train.traffic.json"),
                os.path.join(root, "chipbench/traffic/tiny-train.json"))
    bench = spec.load_benchmark()
    bench["configs"].append({
        "name": "tiny-smallthinker", "source": "ray_tpu.models",
        "file": "chipbench/configs/tiny-smallthinker.json", "reduced": [],
        "why": "test-sized rehearsal"})
    bench["workloads"].append({
        "name": "tiny-smallthinker", "config": "tiny-smallthinker",
        "traffic": "tiny-train", "chips": 1, "why": "test-sized rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-smallthinker")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_tiny_cell_trains_through_jaxtrainer_and_is_correct(root):
    """``JaxTrainer.fit`` -> ``ray_tpu.data`` -> ``make_train_step`` with
    the default step options, the program's loss against the reference's
    (cross entropy + 0.01 x balance over all 8 experts, 2 of them held),
    on fake chips. The result says ``platform: cpu``."""
    code = (
        "import json\n"
        "from chipbench import run\n"
        f"res = run.run_cell('tiny-smallthinker', seed=3000000023, "
        f"seconds=3.0, trace=False, root={root!r}, rehearsal=dict(num_cpus=4, "
        "num_tpus=2, object_store_memory=128 * 1024 * 1024))\n"
        "print('RESULT ' + json.dumps(res))\n")
    env = dict(os.environ, PYTHONPATH=_tinycells.REPO,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    res = json.loads(next(ln for ln in proc.stdout.splitlines()
                          if ln.startswith("RESULT "))[len("RESULT "):])
    assert res["correct"] is True, res["notes"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert {"train_tok_s_chip", "setup_s"} <= set(res["metrics"])
    note = next(n for n in res["notes"] if n.startswith("train:"))
    assert "+ the rest 0.01" in note             # the balance term is in it
