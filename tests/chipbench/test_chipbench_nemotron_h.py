"""What the ``nemotron_h`` configuration (NVIDIA-Nemotron-3-Nano-30B-A3B)
brings to the benchmark: its config file's sums against ``sizes/`` and
``flops/`` and a count by hand, the file against the catalog's numbers,
its ``BENCHMARK.json`` entries and the lists its cell is on (every one
found BY NAME), and the accepted readers and this cell's one new reader
(``step_ssm_carry_ms``) on a hand-written trace of this arch's
instructions. CPU only; the cell itself is rehearsed at its real size by
``test_chipbench_rehearsal.py`` and held to the contract by
``test_chipbench_spec.py``, which pick it up by name."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import spec, xplane

CELL = "train-nemotron3nano-ep16share"
CONFIG = "nemotron-3-nano-30b-a3b-ep16"
NEW_READER = "step_ssm_carry_ms"
FWD = "jit(train_step)/jvp(layers)/closed_call"
BWD = "jit(train_step)/transpose(jvp(layers))/closed_call/checkpoint"
M, A = "attn/attn_linear", "attn/attn_full"
# One device, TWO runs of the train step. (instruction, opcode, us, op_name):
LEAVES = (
    ("in.1", "fusion", 5, f"{FWD}/{M}/attn_qkv/btd,dc->btc/dot_general:"),
    ("conv.2", "fusion", 2, f"{FWD}/{M}/kda_conv/logistic:"),
    ("step.3", "fusion", 1, f"{FWD}/{M}/kda_gate/softplus:"),
    ("cb.4", "fusion", 4, f"{FWD}/{M}/attn_core/jvp()/dot_general:"),
    ("carry.5", "fusion", 3,
     f"{FWD}/{M}/attn_core/jvp()/ssm_carry/while/body/mul:"),
    ("carry.6", "fusion", 4,
     f"{BWD}/{M}/attn_core/transpose(jvp())/ssm_carry/while/body/add:"),
    ("dm.7", "fusion", 6, f"{BWD}/{M}/attn_core/transpose(jvp())/dot_general:"),
    ("norm.8", "fusion", 2,
     f"{BWD}/rematted_computation/{M}/kda_gate/rsqrt:"),
    ("out.9", "fusion", 2, f"{FWD}/{M}/attn_out/bthk,hkd->btd/dot_general:"),
    ("q.10", "fusion", 3, f"{FWD}/{A}/attn_qkv/dot_general:"),
    ("gqa.11", "fusion", 1, f"{FWD}/{A}/attn_gqa/broadcast_in_dim:"),
    ("fwd.12", "custom-call", 4, f"{FWD}/{A}/attn_core/jvp()/pallas_call:"),
    ("sh.13", "fusion", 3, f"{FWD}/moe/moe_shared/dot_general:"),
    ("up.14", "custom-call", 2, f"{FWD}/moe/moe_experts/pallas_call:"),
)
RUNS = 2
LINEAR, CORE, CARRY, CONV, GATE = 29, 17, 7, 2, 3       # us over both runs
FULL, ATTN_KERNEL = 8, 4


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


def _ms(us: float):
    return pytest.approx(us * 1e-3 / RUNS)


def _config():
    data = spec.load_json("chipbench", "configs", CONFIG + ".json")
    return data, spec.model_config(data)


# -- the readers on this arch's instructions ---------------------------------------

def test_the_linear_mixers_readers_and_the_carry_on_the_hand_trace(tmp_path):
    run = _run(tmp_path, _hand())
    assert _read("step_attn_linear_ms", run) == _ms(LINEAR)
    assert _read("step_kda_core_ms", run) == _ms(CORE)
    assert _read("step_kda_conv_ms", run) == _ms(CONV)
    assert _read("step_kda_gate_ms", run) == _ms(GATE)
    # the carry: inside the core, forward and turned round
    assert _read(NEW_READER, run) == _ms(CARRY)
    assert CARRY < CORE
    assert _read("step_attn_full_ms", run) == _ms(FULL)
    assert _read("step_attn_ms", run) == _ms(LINEAR + FULL)
    assert _read("step_attn_core_ms", run) == _ms(CORE + ATTN_KERNEL)
    assert _read("step_attn_kernel_ms", run) == _ms(ATTN_KERNEL)
    assert _read("step_attn_gqa_ms", run) == _ms(1)
    assert _read("step_attn_pos_ms", run) in (None, 0.0)    # nothing rotated
    assert _read("step_moe_shared_ms", run) == _ms(3)
    assert _read("step_moe_experts_ms", run) == _ms(2)
    # the recurrence's count: four state-space layers, 64 heads, 16,384
    # tokens, 2 x 2 x 64 x 128 forward + twice that backward
    assert _read("kda_core_peak_share", run) == pytest.approx(
        100 * 4 * 64 * 16384 * 3 * 4 * 64 * 128 / (CORE * 1e-6 / RUNS)
        / 197e12)
    pairs = 16384 * 16385 // 2
    assert _read("attn_kernel_peak_share", run) == pytest.approx(
        100 * 14 * 32 * 128 * pairs / (ATTN_KERNEL * 1e-6 / RUNS) / 197e12)
    # two matrices an expert: SIX grouped matmuls' worth a step
    assert _read("moe_experts_peak_share", run) == pytest.approx(
        100 * 6 * 4 * 6 / 16 * 2 * 2688 * 1856 * 16384
        / (2 * 1e-6 / RUNS) / 197e12)


def test_the_new_reader_returns_none_with_nothing_to_read(tmp_path):
    assert _read(NEW_READER,
                 {"trace": None, "trace_dir": None, "notes": []}) is None
    # a program without the scope (the parent's has no such layer at all)
    plain = tuple((n, o, us, op.replace("/ssm_carry", ""))
                  for n, o, us, op in LEAVES)
    run = _run(tmp_path, _hand(plain))
    assert _read(NEW_READER, run) is None           # and does not raise
    assert _read("step_kda_core_ms", run) == _ms(CORE)


# -- the entries, every one found by its name --------------------------------------

def test_the_entries_name_the_cell_on_every_list_it_reports():
    """Only what stays true when later PRs append cells, readers or
    further cells to a reader's list: the entries are there with their
    fields, the cell is on the lists it must report and off those it must
    not. Where an entry stands in its list is the contract's to hold."""
    bench = spec.load_benchmark()
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config == {
        "name": CONFIG, "source": "https://huggingface.co/nvidia/"
        "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json",
        "file": f"chipbench/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
        "why": config["why"]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "pretrain-1x16384", "chips": 1,
                    "why": cell["why"]}
    assert "768 rows" in cell["why"] and "16 x" in cell["why"]
    lists = {m["name"]: m["workloads"]
             for m in bench["end_to_end"] + bench["per_layer"]
             if "workloads" in m}
    reports = {name for name, cells in lists.items() if CELL in cells}
    assert {
        NEW_READER,
        # what every train cell reports today
        "train_tok_s_chip", "input_wait_share", "train_step_ms", "mfu",
        "train_device_idle", "step_attn_ms", "step_mlp_ms",
        "step_head_loss_ms", "step_optimizer_ms", "step_recompute_ms",
        "step_unscoped_ms", "input_block_wait_ms", "input_to_device_ms",
        "compiles_in_window", "setup_runtime_s", "setup_compile_s",
        "step_attn_qkv_ms", "step_attn_out_ms", "step_attn_core_ms",
        "setup_chips_wait_s", "setup_backend_s", "setup_trace_lower_s",
        "setup_uncovered_s",
        # a one-chip share cell with a linear mixer and plain attention
        "step_moe_experts_ms", "step_moe_route_ms", "step_moe_shared_ms",
        "moe_experts_peak_share", "moe_load_max", "moe_held_off_balance",
        "moe_full_buffer",
        "step_attn_full_ms", "attn_kernel_peak_share", "step_attn_pos_ms",
        "step_attn_gqa_ms", "step_attn_layout_ms", "step_attn_kernel_ms",
        "attn_outside_peak_share",
        "step_attn_linear_ms", "step_kda_core_ms", "step_kda_conv_ms",
        "step_kda_gate_ms", "kda_core_peak_share"} <= reports
    assert not {"step_kda_kernel_ms", "step_gdn_kernel_ms",
                "step_attn_gate_ms", "step_post_norm_ms",
                "step_attn_window_ms", "step_mla_latent_ms",
                "collective_exposed"} & reports
    new = next(m for m in bench["per_layer"] if m["name"] == NEW_READER)
    assert CELL in new.pop("workloads")
    assert new == {"name": NEW_READER, "unit": "ms", "better": "lower",
                   "source": "device_trace", "layer": "model step",
                   "moves": "train_tok_s_chip"}


# -- the sums -----------------------------------------------------------------------

def test_the_files_sums_are_the_programs_the_flop_functions_and_a_hand_count():
    from chipbench.flops import nemotron_h as flops

    data, cfg = _config()
    p = data["parameters"]
    mixer = (2688 * 10304 + 4096 * 2688 + 6144 * 4 + 6144 + 3 * 64 + 4096)
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    one_expert = 2 * 2688 * 1856
    ffn = 2688 * 128 + 128 + 2 * 2688 * 3712 + 8 * one_expert
    assert (p["state_space_mixer"], p["attention_mixer"],
            p["expert_layer_ffn"]) == (mixer, attn, ffn) == (
        38_742_208, 23_396_352, 100_122_752)
    layers = 4 * (mixer + 2688) + 4 * (ffn + 2688) + attn + 2688
    assert p["layers_M_E_M_E_M_A_E_M_E"] == layers
    assert p["embedding_and_head"] == 2 * 16384 * 2688 == 88_080_384
    total = layers + p["embedding_and_head"] + p["final_norm"]
    assert (p["total"] == total == cfg.num_params() == flops.n_params(cfg)
            == 666_963_456)
    assert p["bytes_at_16_a_parameter"] == 16 * total
    assert 0.25 < 16 * total / 16e9 < 0.7          # over the floor, with room
    spec.load_part("sizes", "nemotron_h").check(data, cfg)
    assert flops.held_share(cfg) == 1 / 16
    assert flops._ssm_params(cfg) == 2688 * 10304 + 4096 * 2688
    assert flops._ssm_params(cfg) + flops._ssm_leaves(cfg) == mixer
    assert flops.matmul_params(cfg) == pytest.approx(
        4 * flops._ssm_params(cfg) + attn
        + 4 * (2688 * 128 + 2 * 2688 * 3712 + 6 / 16 * one_expert)
        + 2688 * 16384)
    assert flops.shared_matmul_params(cfg) == 4 * 2 * 2688 * 3712
    # the recurrence: the state's update and its read, a token and head
    assert flops.kda_core_flops_per_token(cfg) == 4 * 64 * 2 * 2 * 64 * 128
    assert flops.kda_core_flops_per_step(cfg, 16384, 1) == (
        3 * 4 * 64 * 4 * 64 * 128 * 16384)
    pairs = 16384 * 16385 // 2
    kernels = flops.attention_kernel_flops_per_step(cfg, 16384, 1)
    assert kernels == 14 * 32 * 128 * pairs
    assert flops.train_flops_per_token(cfg, 16384) == pytest.approx(
        6 * flops.matmul_params(cfg)
        + 3 * flops.attention_flops_per_token(cfg, 16384))
    # ISSUE 58's reckoning: some 38 TFLOP a step
    assert flops.train_flops_per_token(cfg, 16384) * 16384 == pytest.approx(
        38e12, rel=0.08)
    # SIX grouped matmuls' worth, not nine
    assert flops.experts_train_flops_per_token(cfg) == pytest.approx(
        6 * 4 * 6 / 16 * one_expert)
    assert kernels / flops.attention_kernel_bytes_per_step(cfg, 16384, 1) \
        > 197e12 / 819e9                            # compute-bound


@pytest.mark.parametrize("changes,named", [
    (dict(ssm_state=64), "ssm_state_size: the file states 128"),
    (dict(ssm_groups=4), "n_groups: the file states 8"),
    (dict(ssm_chunk=64), "chunk_size: the file states 128"),
    (dict(kda_conv=3), "conv_kernel: the file states 4"),
    (dict(ssm_conv_bias=False), "use_conv_bias: the file states True"),
    (dict(n_kv_heads=4), "num_key_value_heads: the file states 2"),
    (dict(attn_rope=True), "positions: the file states True"),
    (dict(expert_activation="relu"), "mlp_hidden_act: the file states "
                                     "'relu2'"),
    (dict(expert_gate_scale=1.0),
     "routed_scaling_factor: the file states 2.5"),
    (dict(experts_held=(0, 8)), "n_routed_experts: the file states 8"),
    (dict(expert_top_k=8), "num_experts_per_tok: the file states 6"),
    (dict(router_score="softmax"), "router_score: the file states 'sigmoid'"),
    (dict(d_ff_shared=1856),
     "moe_shared_expert_intermediate_size: the file states 3712"),
    (dict(layer_mixers=("ssm", "ffn", "ssm", "ffn", "attn", "ssm", "ffn",
                        "ssm", "ffn")),
     "hybrid_override_pattern: the file states"),
])
def test_the_size_check_names_what_the_factory_runs_differently(changes,
                                                                named):
    data, cfg = _config()
    check = spec.load_part("sizes", "nemotron_h").check
    with pytest.raises(spec.SpecError, match=named):
        check(data, spec.model_config(data, **changes))


def test_the_config_file_states_the_catalogs_numbers_and_its_cuts():
    data, _ = _config()
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):                 # key by key, where it is
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert data["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if data.get(k) != v]
        assert sorted(differs) == sorted(data["reduced"])
        assert all(data["published"][k] == row["config"][k]
                   for k in data["reduced"])
    assert data["published"] == {"num_hidden_layers": 52,
                                 "n_routed_experts": 128,
                                 "vocab_size": 131072}
    assert (data["num_hidden_layers"], data["n_routed_experts"],
            data["vocab_size"]) == (9, 8, 16384)
    # no width differs from the source, and no other number
    assert (data["hidden_size"], data["head_dim"],
            data["num_attention_heads"], data["num_key_value_heads"],
            data["mamba_num_heads"], data["mamba_head_dim"],
            data["ssm_state_size"], data["n_groups"], data["conv_kernel"],
            data["chunk_size"], data["moe_intermediate_size"],
            data["moe_shared_expert_intermediate_size"],
            data["num_experts_per_tok"], data["routed_scaling_factor"],
            data["max_position_embeddings"]) == (
        2688, 128, 32, 2, 64, 64, 128, 8, 4, 128, 1856, 3712, 6, 2.5, 262144)
    assert data["hybrid_override_pattern"].startswith("MEMEM*EME")
    assert len(data["hybrid_override_pattern"]) == 52       # kept whole
    # the floors: at least four layers behind no dense one, 8 experts, an
    # eighth of the vocabulary
    assert data["n_routed_experts"] * 16 == 128
    assert data["vocab_size"] * 8 == 131072
    assert "16 chips share each layer" in data["deployment"]
    assert data["assumed"]["router_width"] == 128
    for key in ("mixer_equations", "mixer_init", "positions", "experts_are",
                "router_is", "router_bias_is", "learning_rate", "weights",
                "layers_run", "loss_is"):
        assert data["assumed"][key]
    assert any("rescale_prenorm_residual" in d for d in data["departures"])
    assert any("WITHOUT its exchange" in d for d in data["departures"])
    assert data["optimizer"] == {"name": "adamw", "learning_rate": 1e-05,
                                 "weight_decay": 0.1}
    assert "agreement_limits" not in data or data["agreement_limits"]["why"]
    cell = spec.load_cell(CELL)
    traffic = cell["traffic_data"]
    assert (traffic["seq_len"], traffic["rows_per_chip"],
            traffic["fetch_every"], traffic["warmup_steps"],
            traffic["reference_rows"]) == (16384, 1, 4, 2, 1)
