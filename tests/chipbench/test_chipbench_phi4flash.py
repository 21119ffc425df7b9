"""What the ``phi4flash`` configuration (Phi-4-mini-flash-reasoning) brings
to the benchmark: its config file's sums against ``sizes/`` and ``flops/``
and a count by hand, the file against the catalog's numbers, its
``BENCHMARK.json`` entries and the lists its cell is on (every one found BY
NAME, nothing by place or count), and the accepted readers and this cell's
three new ones (``step_gmu_ms``, ``step_attn_cross_ms``,
``step_attn_diff_ms``) on a hand-written trace of this arch's
instructions. CPU only; the cell itself is rehearsed at its real size by
``test_chipbench_rehearsal.py`` and held to the contract by
``test_chipbench_spec.py``, which pick it up by name."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import spec, xplane

CELL = "train-phi4miniflash-1chip"
CONFIG = "phi-4-mini-flash-reasoning-1chip"
NEW_READERS = ("step_gmu_ms", "step_attn_cross_ms", "step_attn_diff_ms")
FWD = "jit(train_step)/jvp(layers)/closed_call"
BWD = "jit(train_step)/transpose(jvp(layers))/closed_call/checkpoint"
M, W, F = "attn/attn_linear", "attn/attn_window", "attn/attn_full"
X = f"{F}/attn_cross"
# One device, TWO runs of the train step. (instruction, opcode, us, op_name):
LEAVES = (
    ("in.1", "fusion", 6, f"{FWD}/{M}/attn_qkv/btd,dc->btc/dot_general:"),
    ("conv.2", "custom-call", 2, f"{FWD}/{M}/kda_conv/pallas_call:"),
    ("step.3", "fusion", 1, f"{FWD}/{M}/kda_gate/softplus:"),
    ("walk.4", "fusion", 9, f"{FWD}/{M}/attn_core/jvp()/while/body/mul:"),
    ("back.5", "fusion", 12,
     f"{BWD}/{M}/attn_core/transpose(jvp())/while/body/add:"),
    ("gate.6", "fusion", 2, f"{BWD}/rematted_computation/{M}/kda_gate/mul:"),
    ("out.7", "fusion", 3, f"{FWD}/{M}/attn_out/btc,cd->btd/dot_general:"),
    ("w1.8", "fusion", 4, f"{FWD}/{M}/gmu/btd,dc->btc/dot_general:"),
    ("w2.9", "fusion", 3, f"{BWD}/{M}/gmu/transpose(jvp())/dot_general:"),
    ("q.10", "fusion", 3, f"{FWD}/{W}/attn_qkv/dot_general:"),
    ("gqa.11", "fusion", 1, f"{FWD}/{W}/attn_gqa/broadcast_in_dim:"),
    ("fwd.12", "custom-call", 2, f"{FWD}/{W}/attn_core/jvp()/pallas_call:"),
    ("sub.13", "fusion", 1, f"{FWD}/{W}/attn_diff/rsqrt:"),
    ("fwd.14", "custom-call", 5, f"{FWD}/{F}/attn_core/jvp()/pallas_call:"),
    ("sub.15", "fusion", 1, f"{BWD}/{F}/attn_diff/transpose(jvp())/mul:"),
    ("q.16", "fusion", 2, f"{FWD}/{X}/attn_qkv/dot_general:"),
    ("fwd.17", "custom-call", 5, f"{FWD}/{X}/attn_core/jvp()/pallas_call:"),
    ("sub.18", "fusion", 1, f"{FWD}/{X}/attn_diff/rsqrt:"),
    ("out.19", "fusion", 2, f"{FWD}/{F}/attn_out/bthk,hkd->btd/dot_general:"),
)
RUNS = 2
LINEAR, CORE, CONV, GATE, GMU = 42, 21, 2, 3, 7         # us over both runs
WINDOW, FULL, CROSS, DIFF, KERNELS = 7, 16, 8, 3, 12


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

def test_the_accepted_readers_and_the_three_new_ones_on_the_hand_trace(
        tmp_path):
    from chipbench.flops import phi4flash as flops

    run = _run(tmp_path, _hand())
    assert _read("step_gmu_ms", run) == _ms(GMU)
    assert _read("step_attn_cross_ms", run) == _ms(CROSS)
    assert _read("step_attn_diff_ms", run) == _ms(DIFF)
    # the memory units are linear-mixer time, the cross layer full-attention
    # time: the new scopes lie INSIDE the accepted ones
    assert _read("step_attn_linear_ms", run) == _ms(LINEAR)
    assert _read("step_kda_core_ms", run) == _ms(CORE)
    assert _read("step_kda_conv_ms", run) == _ms(CONV)
    assert _read("step_kda_gate_ms", run) == _ms(GATE)
    assert _read("step_attn_window_ms", run) == _ms(WINDOW)
    assert _read("step_attn_full_ms", run) == _ms(FULL)
    assert _read("step_attn_ms", run) == _ms(LINEAR + WINDOW + FULL)
    # every Pallas call under ``attn``: the attention kernels AND the chain
    assert _read("step_attn_kernel_ms", run) == _ms(KERNELS + CONV)
    assert _read("step_attn_core_ms", run) == _ms(CORE + KERNELS)
    assert _read("step_attn_gqa_ms", run) == _ms(1)
    assert _read("step_attn_pos_ms", run) in (None, 0.0)    # nothing rotated
    _, cfg = _config()
    assert _read("kda_core_peak_share", run) == pytest.approx(
        100 * flops.kda_core_flops_per_step(cfg, 16384, 1)
        / (CORE * 1e-6 / RUNS) / 197e12)
    assert _read("attn_kernel_peak_share", run) == pytest.approx(
        100 * flops.attention_kernel_flops_per_step(cfg, 16384, 1)
        / (KERNELS * 1e-6 / RUNS) / 197e12)


@pytest.mark.parametrize("name,scope", zip(
    NEW_READERS, ("gmu", "attn_cross", "attn_diff")))
def test_a_new_reader_returns_none_with_nothing_to_read(tmp_path, name,
                                                        scope):
    assert _read(name, {"trace": None, "trace_dir": None, "notes": []}) is None
    # a program without the scope (the parent's has no such layer at all)
    plain = tuple((n, o, us, op.replace(f"/{scope}", ""))
                  for n, o, us, op in LEAVES)
    run = _run(tmp_path, _hand(plain))
    assert _read(name, run) is None                 # and does not raise
    assert _read("step_kda_core_ms", run) == _ms(CORE)


# -- the entries, every one found by its name --------------------------------------

def test_the_entries_name_the_cell_on_every_list_it_reports():
    """Only what stays true when later PRs append cells, readers or further
    cells to a reader's list (ROADMAP B11 (7)): fields, ``CELL in
    workloads``, lists BY NAME with ``<=``."""
    bench = spec.load_benchmark()
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config == {
        "name": CONFIG, "source": "https://huggingface.co/microsoft/"
        "Phi-4-mini-flash-reasoning/blob/main/config.json",
        "file": f"chipbench/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers", "vocab_size"],
        "why": config["why"]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "pretrain-1x16384", "chips": 1,
                    "why": cell["why"]}
    lists = {m["name"]: m["workloads"]
             for m in bench["end_to_end"] + bench["per_layer"]
             if "workloads" in m}
    reports = {name for name, cells in lists.items() if CELL in cells}
    assert {
        *NEW_READERS,
        # what every train cell reports today
        "train_tok_s_chip", "input_wait_share", "train_step_ms", "mfu",
        "train_device_idle", "step_attn_ms", "step_mlp_ms",
        "step_head_loss_ms", "step_optimizer_ms", "step_recompute_ms",
        "step_unscoped_ms", "input_block_wait_ms", "input_to_device_ms",
        "compiles_in_window", "setup_runtime_s", "setup_compile_s",
        "step_attn_qkv_ms", "step_attn_out_ms", "step_attn_core_ms",
        "setup_chips_wait_s", "setup_backend_s", "setup_trace_lower_s",
        "setup_uncovered_s",
        # an arch that is not gpt2, GQA, rows over 1,024
        "step_attn_pos_ms", "step_attn_gqa_ms", "step_attn_layout_ms",
        "step_attn_kernel_ms", "attn_outside_peak_share",
        # window and full attention, a linear mixer
        "step_attn_window_ms", "step_attn_full_ms", "attn_kernel_peak_share",
        "step_attn_linear_ms", "step_kda_core_ms", "step_kda_conv_ms",
        "step_kda_gate_ms", "kda_core_peak_share"} <= reports
    # a dense model: no router; lists accepted tests pin to one cell
    assert not {name for name in reports if "moe" in name}
    assert not {"step_mla_latent_ms", "step_ssm_carry_ms",
                "step_kda_kernel_ms", "step_gdn_kernel_ms",
                "step_attn_gate_ms", "step_post_norm_ms",
                "collective_exposed"} & reports
    for name in NEW_READERS:
        new = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in new.pop("workloads")
        assert new == {"name": name, "unit": "ms", "better": "lower",
                       "source": "device_trace", "layer": "model step",
                       "moves": "train_tok_s_chip"}


# -- the sums -----------------------------------------------------------------------

def test_the_files_sums_are_the_programs_the_flop_functions_and_a_hand_count():
    from chipbench.flops import _attn_proj
    from chipbench.flops import phi4flash as flops

    data, cfg = _config()
    p = data["parameters"]
    d, inner = 2560, 5120
    mamba_matrices = 2 * d * inner + inner * 192 + 160 * inner + inner * d
    mamba = mamba_matrices + inner + inner * 16 + 5 * inner + inner
    attn = d * 5120 + 5120 + d * d + d + 4 * 64 + 128
    gmu = 2 * d * inner
    cross = 2 * (d * d + d) + 4 * 64 + 128
    mlp = 3 * d * 10240
    assert (p["mamba1_mixer"], p["attention_mixer"], p["memory_unit_mixer"],
            p["cross_mixer"], p["mlp"], p["layer_norms"]) == (
        mamba, attn, gmu, cross, mlp, 4 * d) == (
        41_241_600, 19_668_864, 26_214_400, 13_112_704, 78_643_200, 10_240)
    layer = lambda mixer: mixer + mlp + 4 * d
    assert (p["mamba1_layer"], p["attention_layer"], p["memory_unit_layer"],
            p["cross_layer"]) == tuple(map(layer, (mamba, attn, gmu, cross)))
    layers = 2 * layer(mamba) + 2 * layer(attn) + layer(gmu) + layer(cross)
    assert p["layers_14_to_19"] == layers == 633_068_672
    assert p["embedding_and_head"] == 25088 * d == 64_225_280   # ONE matrix
    assert 25088 == 196 * 128 and 25088 >= 200064 / 8 > 25088 - 128
    total = layers + p["embedding_and_head"] + p["final_norm"]
    assert (p["total"] == total == cfg.num_params() == flops.n_params(cfg)
            == 697_299_072)
    assert p["bytes_at_16_a_parameter"] == 16 * total
    assert 0.25 < 16 * total / 16e9 < 0.75          # over the floor, with room
    assert p["published_depth_total"] == 3_852_562_944
    spec.load_part("sizes", "phi4flash").check(data, cfg)
    # the matrices a token passes through: the tied head ONCE, the cross
    # layer without key / value projections
    assert flops.matmul_params(cfg) == (
        2 * mamba_matrices + 2 * (d * 5120 + d * d) + gmu + 2 * d * d
        + 6 * mlp + d * 25088)
    # the recurrence as written: six operations a token, channel and state
    # index, over the cut's TWO Mamba-1 layers (14 and 16)
    assert flops.kda_core_flops_per_token(cfg) == 2 * 6 * inner * 16
    assert flops.kda_core_flops_per_step(cfg, 16384, 1) == (
        3 * 2 * 6 * inner * 16 * 16384)
    # the kernels: two calls a layer, 20 query pairs, four matmuls 64 wide
    # and three 128 wide a visible pair; a band of 512 and three triangles
    triangle = 16384 * 16385 // 2
    band = 512 * 513 // 2 + (16384 - 512) * 512
    kernels = flops.attention_kernel_flops_per_step(cfg, 16384, 1)
    assert kernels == 2 * 2 * 20 * (4 * 64 + 3 * 128) * (band
                                                          + 2 * triangle)
    assert kernels == pytest.approx(14.2e12, rel=0.01)
    assert flops.train_flops_per_token(cfg, 16384) == pytest.approx(
        6 * flops.matmul_params(cfg)
        + 3 * flops.attention_flops_per_token(cfg, 16384))
    assert flops.attention_flops_per_token(cfg, 16384) == pytest.approx(
        2 * 2 * 20 * 192 * (band + 2 * triangle) / 16384
        + flops.kda_core_flops_per_token(cfg))
    # ISSUE 62's reckoning: some 83 model TFLOP a step
    assert flops.train_flops_per_token(cfg, 16384) * 16384 == pytest.approx(
        83e12, rel=0.04)
    assert kernels / flops.attention_kernel_bytes_per_step(cfg, 16384, 1) \
        > 197e12 / 819e9                            # compute-bound
    # ``attn_outside_peak_share`` counts plain q / k / v / out for every
    # layer: it UNDERCOUNTS this cell's projections and cannot pass 100
    assert 6 * _attn_proj.projection_params(cfg) == 117_964_800 < (
        2 * mamba_matrices + 2 * (d * 5120 + d * d) + gmu + 2 * d * d)


@pytest.mark.parametrize("changes,named", [
    (dict(ssm_state=8), "mamba_d_state: the file states 16"),
    (dict(kda_conv=3), "mamba_d_conv: the file states 4"),
    (dict(ssm_dt_rank=128), "mamba_dt_rank: the file states 160"),
    (dict(ssm_conv_bias=False), "the convolution's bias: the file states "
                                "True"),
    (dict(sliding_window=256), "sliding_window: the file states 512"),
    (dict(tie_embeddings=False), "tie_word_embeddings: the file states True"),
    (dict(first_layer=12), "first_layer: the file states 14"),
    (dict(d_ff=8192), "intermediate_size: the file states 10240"),
    (dict(n_kv_heads=10), "num_key_value_heads: the file states 20"),
    (dict(vocab_size=200064), "vocab_size: the file states 25088"),
])
def test_the_size_check_names_what_the_factory_runs_differently(changes,
                                                                named):
    data, cfg = _config()
    check = spec.load_part("sizes", "phi4flash").check
    with pytest.raises(spec.SpecError, match=named):
        check(data, spec.model_config(data, **changes))


def test_the_config_file_states_the_catalogs_numbers_and_its_cuts():
    data, cfg = _config()
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):                 # key by key, where it is
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        assert data["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if data.get(k) != v]
        assert sorted(differs) == sorted(data["reduced"])
        assert all(data["published"][k] == row["config"][k]
                   for k in data["reduced"])
    assert data["published"] == {"num_hidden_layers": 32,
                                 "vocab_size": 200064}
    assert (data["num_hidden_layers"], data["vocab_size"]) == (6, 25088)
    assert data["factory_kwargs"] == {"n_layers": 6, "first_layer": 14,
                                      "vocab_size": 25088}
    assert cfg.layer_mixers == ("ssm1", "attn", "ssm1", "attn", "gmu",
                                "cross")
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank", "attention_bias", "differential_attention",
                "head_pairs", "window_is", "positions", "memory_is",
                "mixer_init"):
        assert key in data["assumed"], key
    assert len(data["departures"]) >= 4 and "eight slices" in data[
        "deployment"]
    assert data["optimizer"] == {"name": "adamw", "learning_rate": 1e-05,
                                 "weight_decay": 0.1}
