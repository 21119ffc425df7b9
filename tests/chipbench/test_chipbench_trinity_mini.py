"""What the ``afmoe`` configuration (Trinity-Mini) brings to the benchmark:
its two per-layer readers on a hand-written trace whose answers are
computed by hand (and with nothing to read: the parent's program, no
trace), the readers that are there on this arch's instructions, its FLOP
functions against the program's own parameter count and a count by hand,
its size check, its config file against the source's numbers, its
``BENCHMARK.json`` entries, and its cell at test size through the real
entry point (``JaxTrainer.fit`` on fake chips), added to a temporary copy
of the benchmark the way a later PR adds a cell. CPU only; the cell itself
is rehearsed at its real size by ``test_chipbench_rehearsal.py`` and held
to the contract by ``test_chipbench_spec.py``, which pick it up by name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import _tinycells
from chipbench import spec, xplane

CELL = "train-trinitymini-ep16share"
CONFIG = "trinity-mini-26b-a3b-ep16"
NEW_READERS = ("step_attn_gate_ms", "step_post_norm_ms")
FWD = "jit(train_step)/jvp(layers)/closed_call"
BWD = "jit(train_step)/transpose(jvp(layers))/closed_call/checkpoint"
W, G = "attn/attn_window", "attn/attn_full"
# One device, TWO runs of the train step. (instruction, us, op_name):
LEAVES = (
    ("qkv.1", "fusion", 4, f"{FWD}/{W}/attn_qkv/dot_general:"),
    ("hn.2", "fusion", 1, f"{FWD}/{W}/attn_pos/mul:"),
    ("fwd.3", "custom-call", 6, f"{FWD}/{W}/attn_core/jvp()/pallas_call:"),
    ("gate.4", "fusion", 3, f"{FWD}/{W}/attn_gate/btd,dhk->bthk/dot_general:"),
    ("gate.5", "fusion", 1, f"{FWD}/{G}/attn_gate/logistic:"),
    ("out.6", "fusion", 2, f"{FWD}/{W}/attn_out/dot_general:"),
    ("pn.7", "fusion", 1, f"{FWD}/{W}/post_norm/rsqrt:"),
    ("pn.8", "fusion", 2, f"{FWD}/moe/post_norm/mul:"),
    ("pn.9", "fusion", 1, f"{BWD}/rematted_computation/mlp/post_norm/mul:"),
    ("gate.10", "fusion", 5,
     f"{BWD}/{W}/attn_gate/transpose(jvp())/dot_general:"),
    ("bwd.11", "custom-call", 8,
     f"{BWD}/{G}/attn_core/transpose(jvp())/pallas_call:"),
    ("sh.12", "fusion", 2, f"{FWD}/moe/moe_shared/dot_general:"),
)
RUNS = 2
# us over both runs, by hand
GATE, POST, WINDOW, FULL, KERNEL, ATTN = 9, 4, 22, 9, 14, 31


def _hand(leaves=LEAVES) -> str:
    events, metadata, at = [], [], 0
    for i, (name, opcode, us, op_name) in enumerate(leaves, 1):
        events.append(f"events {{ metadata_id: {i} offset_ps: {at} "
                      f"duration_ps: {us * 1_000_000} }}")
        metadata.append(
            f'event_metadata {{ key: {i} value {{ id: {i} name: '
            f'"%{name} = bf16[8]{{0}} {opcode}(bf16[8]{{0}} %x)" '
            f'stats {{ metadata_id: 1 str_value: "{op_name}" }} }} }}')
        at += us * 1_000_000
    step, half = len(leaves) + 1, at // RUNS
    return (
        'planes { id: 1 name: "/device:TPU:0"\n'
        '  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000\n    '
        + "\n    ".join(events) + "\n  }\n"
        '  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000\n'
        f"    events {{ metadata_id: {step} offset_ps: 0 "
        f"duration_ps: {half} }}\n"
        f"    events {{ metadata_id: {step} offset_ps: {half} "
        f"duration_ps: {at - half} }}\n  }}\n  "
        + "\n  ".join(metadata) + "\n"
        f'  event_metadata {{ key: {step} value {{ id: {step} '
        'name: "jit_train_step(123)" } }\n'
        '  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n}\n')


def _run(tmp_path, text: str) -> dict:
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True, exist_ok=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return {"trace": xplane.load(xplane.find_xplane(str(tmp_path))),
            "trace_dir": str(tmp_path), "notes": [],
            "cell": spec.load_cell(CELL),
            "train": {"tokens_per_step": 16384},
            "peaks": spec.load_peaks("TPU v5 lite")}


def _read(name: str, run: dict):
    return spec.load_part("layer_metrics", name).read(run)


def _ms(us: float) -> float:
    return pytest.approx(us * 1e-3 / RUNS)


def test_the_new_readers_on_the_hand_trace(tmp_path):
    run = _run(tmp_path, _hand())
    assert _read("step_attn_gate_ms", run) == _ms(GATE)
    assert _read("step_post_norm_ms", run) == _ms(POST)
    # the readers that were there: the two kinds of layer add up to attn,
    # the output norms of the FFNs are mlp's / moe's own
    assert _read("step_attn_window_ms", run) == _ms(WINDOW)
    assert _read("step_attn_full_ms", run) == _ms(FULL)
    assert _read("step_attn_ms", run) == _ms(ATTN)
    assert _read("step_attn_kernel_ms", run) == _ms(KERNEL)
    assert _read("step_attn_pos_ms", run) == _ms(1)
    assert _read("step_attn_out_ms", run) == _ms(2)
    assert _read("step_mlp_ms", run) == _ms(2 + 1 + 2)
    assert _read("step_moe_shared_ms", run) == _ms(2)
    # 7 us of kernels a step against the cell's visible pairs, 7 matmuls
    pairs = 4 * (2048 * 2049 // 2 + (16384 - 2048) * 2048) + 16384 * 16385 // 2
    assert _read("attn_kernel_peak_share", run) == pytest.approx(
        100 * 14 * 32 * 128 * pairs / 7.0e-6 / 197e12)
    # ``flops/_attn_proj.py`` counts q, k, v and out, not the gate's
    # projection: 19 of this arch's 27 M a layer (an undercount)
    proj = 2 * 2048 * 4096 + 2 * 2048 * 512
    assert _read("attn_outside_peak_share", run) == pytest.approx(
        100 * 5 * 6 * proj * 16384 / ((ATTN - KERNEL) * 1e-6 / RUNS) / 197e12)


@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_return_none_with_nothing_to_read(name, tmp_path):
    assert _read(name, {"trace": None, "trace_dir": None, "notes": []}) is None
    # a program without the new scopes (the parent's): nothing to read
    plain = tuple((n, o, us, op.replace("/attn_gate/", "/").replace(
        "/post_norm/", "/")) for n, o, us, op in LEAVES)
    run = _run(tmp_path, _hand(plain))
    assert _read(name, run) is None
    assert _read("step_attn_ms", run) == _ms(ATTN)


def test_the_entries_are_appended_and_the_readers_are_this_cells_alone():
    bench = spec.load_benchmark()

    def place(section, name):
        return [e["name"] for e in bench[section]].index(name)

    # behind everything PR 40 left (a later PR appends behind these)
    assert place("configs", CONFIG) > place("configs",
                                            "kimi-linear-48b-a3b-ep32")
    assert place("workloads", CELL) > place("workloads",
                                            "train-kimilinear-ep32share")
    assert bench["configs"][place("configs", CONFIG)]["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    cell = bench["workloads"][place("workloads", CELL)]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "pretrain-1x16384", "chips": 1,
                    "why": cell["why"]}
    at = [place("per_layer", name) for name in NEW_READERS]
    assert at[0] > place("per_layer", "step_kda_kernel_ms")
    assert at[1] == at[0] + 1
    for m in (bench["per_layer"][i] for i in at):
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "model step",
                     "moves": "train_tok_s_chip", "workloads": [CELL]}
    # appended, never put in the middle of a list
    lists = [m["workloads"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", [])]
    assert all(w.index(CELL) > w.index(c) for w in lists for c in w
               if c in ("train-gpt2xl-1chip", "train-kimilinear-ep32share"))
    reports = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    gpt2 = {m["name"] for m in bench["per_layer"]
            if "train-gpt2xl-1chip" in m.get("workloads", [])}
    assert reports == gpt2 | set(NEW_READERS) | {
        "step_moe_experts_ms", "step_moe_route_ms", "moe_experts_peak_share",
        "step_moe_shared_ms", "moe_load_max", "moe_held_off_balance",
        "moe_full_buffer", "step_attn_window_ms", "step_attn_full_ms",
        "attn_kernel_peak_share", "step_attn_pos_ms", "step_attn_gqa_ms",
        "step_attn_layout_ms", "step_attn_kernel_ms",
        "attn_outside_peak_share"}
    assert not {"collective_exposed", "step_mla_latent_ms",
                "step_attn_linear_ms", "step_kda_core_ms"} & reports
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"train_tok_s_chip", "setup_s"}


def test_afmoe_flop_functions_count_the_programs_parameters():
    from chipbench.flops import afmoe as flops

    data = spec.load_json("chipbench", "configs", CONFIG + ".json")
    cfg = spec.model_config(data)
    assert flops.n_params(cfg) == cfg.num_params() == 504_147_712
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512          # q, gate, out; k, v
    assert flops._attention_params(cfg) == attn == 27_262_976
    one_expert, dense = 3 * 2048 * 1024, 3 * 2048 * 6144
    assert flops.held_share(cfg) == 1 / 16
    assert flops.matmul_params(cfg) == pytest.approx(
        5 * attn + 4 * (2048 * 128 + 8 / 16 * one_expert + one_expert)
        + dense + 2048 * 25024)
    assert flops.matmul_params(cfg) == pytest.approx(264.1e6, rel=1e-3)
    # a sliding row's query i sees min(i + 1, 2048) keys
    sliding = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    full = 16384 * 16385 // 2
    assert flops.visible_pairs(16384, 2048) == sliding == sum(
        min(i + 1, 2048) for i in range(16384))
    assert flops.visible_pairs(16384, None) == full
    assert flops.layer_windows(cfg) == [2048, 2048, None, 2048, 2048]
    kernels = flops.attention_kernel_flops_per_step(cfg, 16384, 1)
    assert kernels == 14 * 32 * 128 * (4 * sliding + full)
    assert kernels == pytest.approx(14.9e12, rel=2e-3)
    assert 14 * 32 * 128 * full == pytest.approx(7.7e12, rel=2e-3)
    assert 14 * 32 * 128 * sliding == pytest.approx(1.8e12, rel=3e-3)
    assert flops.attention_flops_per_token(cfg, 16384) * 16384 == (
        pytest.approx(kernels * 2 / 7))
    assert flops.train_flops_per_token(cfg, 16384) == pytest.approx(
        6 * flops.matmul_params(cfg)
        + 3 * flops.attention_flops_per_token(cfg, 16384))
    assert 6 * flops.matmul_params(cfg) * 16384 == pytest.approx(26.0e12,
                                                                 rel=2e-3)
    assert flops.experts_train_flops_per_token(cfg) * 16384 == pytest.approx(
        6 * 4 * 0.5 * one_expert * 16384)
    assert kernels / flops.attention_kernel_bytes_per_step(cfg, 16384, 1) \
        > 197e12 / 819e9


@pytest.mark.parametrize("changes,named", [
    (dict(attn_gate=False), "attention_gate: the file states True"),
    (dict(qk_norm=True), "qk_norm: the file states 'head'"),
    (dict(post_norm=False), "post_norm: the file states True"),
    (dict(embed_scale=1.0), "mup_enabled: the file states True"),
    (dict(first_layer=2), "first_layer: the file states 1"),
    (dict(first_layer=2), "layer_types: the file states \\['sliding"),
    (dict(sliding_window=4096), "sliding_window: the file states 2048"),
    (dict(n_kv_heads=8), "num_key_value_heads: the file states 4"),
    (dict(experts_held=(0, 8)), "num_experts: the file states 8"),
    (dict(d_ff_dense=4096), "intermediate_size: the file states 6144"),
    (dict(d_ff_shared=2048), "num_shared_experts x moe_intermediate_size: "
                             "the file states 1024"),
    (dict(router_score="softmax"), "score_func: the file states 'sigmoid'"),
    (dict(expert_gate_scale=1.0), "route_scale: the file states 2.826"),
    (dict(expert_top_k=6), "num_experts_per_tok: the file states 8"),
    (dict(norm_eps=1e-6), "rms_norm_eps: the file states 1e-05"),
    (dict(router_bias_rate=0.01), "load_balance_coeff: the file states 0.001"),
    (dict(max_seq_len=16384),
     "max_position_embeddings: the file states 131072"),
])
def test_afmoe_size_check_names_what_the_factory_runs_differently(
        changes, named):
    data = spec.load_json("chipbench", "configs", CONFIG + ".json")
    check = spec.load_part("sizes", "afmoe").check
    check(data, spec.model_config(data))
    with pytest.raises(spec.SpecError, match=named):
        check(data, spec.model_config(data, **changes))


def test_the_config_file_states_the_sources_numbers_and_its_cuts():
    data = spec.load_json("chipbench", "configs", CONFIG + ".json")
    assert data["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "num_experts", "vocab_size"]
    assert data["published"] == {"num_hidden_layers": 32,
                                 "num_dense_layers": 2, "num_experts": 128,
                                 "vocab_size": 200192}
    assert (data["num_hidden_layers"], data["num_dense_layers"],
            data["num_experts"], data["vocab_size"]) == (5, 1, 8, 25024)
    # no width differs from the source, and no other number
    assert (data["hidden_size"], data["intermediate_size"],
            data["moe_intermediate_size"], data["head_dim"],
            data["num_attention_heads"], data["num_key_value_heads"],
            data["num_experts_per_tok"], data["num_shared_experts"],
            data["route_scale"], data["rope_theta"], data["sliding_window"],
            data["max_position_embeddings"], data["rms_norm_eps"],
            data["load_balance_coeff"], data["global_attn_every_n_layers"],
            data["n_group"], data["topk_group"], data["num_expert_groups"],
            data["num_limited_groups"]) == (
        2048, 6144, 1024, 128, 32, 4, 8, 1, 2.826, 10000, 2048, 131072, 1e-5,
        0.001, 4, 1, 1, 1, 1)
    assert data["layer_types"] == (["sliding_attention"] * 3
                                   + ["full_attention"]) * 8
    assert (data["model_type"], data["score_func"], data["route_norm"],
            data["mup_enabled"], data["hidden_act"], data["rope_scaling"],
            data["tie_word_embeddings"], data["use_grouped_mm"]) == (
        "afmoe", "sigmoid", True, True, "silu", None, False, True)
    # the floors: a dense layer and a whole period of four, 8 experts, an
    # eighth of the vocabulary
    assert data["num_hidden_layers"] - data["num_dense_layers"] == 4
    assert data["vocab_size"] * 8 == 200192
    assert data["num_experts"] * 16 == 128
    assert "16 chips share each layer" in data["deployment"]
    assert "504.1 M params" in data["deployment"]
    assert data["optimizer"] == {"name": "adamw", "learning_rate": 1e-5,
                                 "weight_decay": 0.1}
    for key in ("attention_gate", "qk_norm_is", "four_norms", "mup_scales",
                "router_bias_rule", "router_bias_init", "layers_run",
                "learning_rate", "loss"):
        assert data["assumed"][key]
    assert "agreement_limits" not in data or data["agreement_limits"]["why"]
    cell = spec.load_cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-1x16384")
    traffic = cell["traffic_data"]
    assert (traffic["seq_len"], traffic["rows_per_chip"],
            traffic["fetch_every"], traffic["warmup_steps"],
            traffic["reference_rows"]) == (16384, 1, 4, 2, 1)


# -- the cell at test size through JaxTrainer.fit ----------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with ``tiny-afmoe`` added: a config file of
    the ``afmoe`` arch at test size and the tiny traffic file; the arch's
    own reference, FLOP count, size check and readers are the
    repository's."""
    root = os.path.join(str(tmp_path_factory.mktemp("afmoe")), "root")
    shutil.copytree(os.path.join(_tinycells.REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    name = "tiny-afmoe"
    shutil.copy(os.path.join(_tinycells.TINY, name + ".config.json"),
                os.path.join(root, "chipbench", "configs", name + ".json"))
    shutil.copy(os.path.join(_tinycells.TINY, "tiny-train.traffic.json"),
                os.path.join(root, "chipbench/traffic/tiny-train.json"))
    bench = spec.load_benchmark()
    bench["configs"].append({
        "name": name, "source": "ray_tpu.models",
        "file": f"chipbench/configs/{name}.json", "reduced": [],
        "why": "test-sized rehearsal"})
    bench["workloads"].append({
        "name": name, "config": name, "traffic": "tiny-train", "chips": 1,
        "why": "test-sized rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_tiny_cell_trains_through_jaxtrainer_and_reports_the_gate(root):
    """``JaxTrainer.fit`` -> ``ray_tpu.data`` -> ``make_train_step`` with
    the default step options, the program's logits against the
    reference's token by token (a dense sliding layer, then S F S S with
    4 of 8 experts held), on fake chips; ``attn_gate_mean`` is among the
    last step's counters."""
    data = spec.load_json("chipbench", "configs", "tiny-afmoe.json",
                          root=root)
    spec.load_part("sizes", "afmoe").check(data, spec.model_config(data))
    code = (
        "import json\n"
        "from chipbench import run\n"
        "res = run.run_cell('tiny-afmoe', seed=3900000029, "
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
    assert "+ the rest 0.00000" in note          # no router term in the loss
    counters = next(n for n in res["notes"]
                    if n.startswith("the last step's counters:"))
    gate = float(counters.split("attn_gate_mean ")[1].split(",")[0])
    assert gate == pytest.approx(0.5, abs=0.02)
    for name in ("router_bias_absmax", "moe_held_share", "moe_full_buffer"):
        assert name in counters
    assert all(f"check {name}: ok" in res["notes"] for name in (
        "program_agrees_with_reference", "first_step_is_the_compared_forward",
        "step_moves_the_weights", "step_compiled_once"))
