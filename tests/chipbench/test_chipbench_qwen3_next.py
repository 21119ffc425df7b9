"""What the ``qwen3_next`` configuration (Qwen3-Next-80B-A3B) brings to the
benchmark: its config file's sums against ``sizes/`` and ``flops/`` and a
count by hand, the file against the catalog's numbers, its
``BENCHMARK.json`` entries and the lists its cell is on (every one found
BY NAME: no count of entries and no place from the end is pinned, so a
later PR's cell breaks nothing here), the accepted readers and this
cell's one new reader on a hand-written trace of this arch's
instructions, the reference's controls at test size, and the cell at test
size through the real entry point (``JaxTrainer.fit`` on fake chips),
added to a temporary copy of the benchmark the way a later PR adds a
cell. CPU only; the cell itself is rehearsed at its real size by
``test_chipbench_rehearsal.py`` and held to the contract by
``test_chipbench_spec.py``, which pick it up by name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import _tinycells
from chipbench import spec, xplane

CELL = "train-qwen3next-ep16share"
CONFIG = "qwen3-next-80b-a3b-ep16"
NEW_READER = "step_gdn_kernel_ms"
GPT2, KIMI = "train-gpt2xl-1chip", "train-kimilinear-ep32share"
FWD = "jit(train_step)/jvp(layers)/closed_call"
BWD = "jit(train_step)/transpose(jvp(layers))/closed_call/checkpoint"
D, A = "attn/attn_linear", "attn/attn_full"
# One device, TWO runs of the train step. (instruction, opcode, us, op_name):
LEAVES = (
    ("qkvz.1", "fusion", 4, f"{FWD}/{D}/attn_qkv/btd,dc->btc/dot_general:"),
    ("conv.2", "custom-call", 2, f"{FWD}/{D}/kda_conv/pallas_call:"),
    ("gate.3", "fusion", 1, f"{FWD}/{D}/kda_gate/softplus:"),
    ("rule.4", "custom-call", 6, f"{FWD}/{D}/attn_core/jvp()/pallas_call:"),
    ("rule.5", "custom-call", 10,
     f"{BWD}/{D}/attn_core/transpose(jvp())/pallas_call:"),
    ("rows.6", "fusion", 1, f"{FWD}/{D}/attn_core/transpose:"),
    ("norm.7", "fusion", 2,
     f"{BWD}/rematted_computation/{D}/kda_gate/logistic:"),
    ("out.8", "fusion", 2, f"{FWD}/{D}/attn_out/bthk,hkd->btd/dot_general:"),
    ("q.9", "fusion", 3, f"{FWD}/{A}/attn_qkv/dot_general:"),
    ("rope.10", "fusion", 1, f"{FWD}/{A}/attn_pos/concatenate:"),
    ("gqa.11", "fusion", 1, f"{FWD}/{A}/attn_gqa/broadcast_in_dim:"),
    ("fwd.12", "custom-call", 4, f"{FWD}/{A}/attn_core/jvp()/pallas_call:"),
    ("gate.13", "fusion", 2, f"{FWD}/{A}/attn_gate/logistic:"),
    ("sh.14", "fusion", 3, f"{FWD}/moe/moe_shared/dot_general:"),
)
RUNS = 2
# us over both runs, by hand
LINEAR, CORE, CONV, GATE, GDN_KERNEL = 28, 17, 2, 3, 18
FULL, ATTN_KERNEL = 11, 4


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

def test_the_linear_mixers_readers_on_the_hand_trace(tmp_path):
    run = _run(tmp_path, _hand())
    assert _read("step_attn_linear_ms", run) == _ms(LINEAR)
    assert _read("step_kda_core_ms", run) == _ms(CORE)
    assert _read("step_kda_conv_ms", run) == _ms(CONV)
    assert _read("step_kda_gate_ms", run) == _ms(GATE)
    # the rule's two kernels AND the chains': every pallas_call under
    # attn_linear, under the accepted name and under this cell's own
    assert _read("step_kda_kernel_ms", run) == _ms(GDN_KERNEL)
    assert _read(NEW_READER, run) == _ms(GDN_KERNEL)
    # the two kinds of layer add up to attn; the gate's reader is not on
    # this cell's list but its time is inside attn_full
    assert _read("step_attn_full_ms", run) == _ms(FULL)
    assert _read("step_attn_ms", run) == _ms(LINEAR + FULL)
    assert _read("step_attn_core_ms", run) == _ms(CORE + ATTN_KERNEL)
    assert _read("step_attn_kernel_ms", run) == _ms(GDN_KERNEL + ATTN_KERNEL)
    assert _read("step_attn_qkv_ms", run) == _ms(4 + 3)
    assert _read("step_attn_pos_ms", run) == _ms(1)
    assert _read("step_attn_gqa_ms", run) == _ms(1)
    assert _read("step_moe_shared_ms", run) == _ms(3)
    # the recurrence's count: three DeltaNet layers, 32 VALUE heads, 16,384
    # tokens, 6 x 128 x 128 forward + twice that backward
    assert _read("kda_core_peak_share", run) == pytest.approx(
        100 * 3 * 32 * 16384 * 18 * 128 * 128 / (CORE * 1e-6 / RUNS)
        / 197e12)
    # ONE attention layer's visible pairs, 16 query heads 256 wide
    pairs = 16384 * 16385 // 2
    assert _read("attn_kernel_peak_share", run) == pytest.approx(
        100 * 14 * 16 * 256 * pairs / (ATTN_KERNEL * 1e-6 / RUNS) / 197e12)


def test_the_new_reader_returns_none_with_nothing_to_read(tmp_path):
    assert _read(NEW_READER,
                 {"trace": None, "trace_dir": None, "notes": []}) is None
    # a delta rule in plain XLA (the scan; the parent's program has no
    # such layers at all): no kernel leaf under attn_linear
    plain = tuple((n, "fusion", us, op.replace("pallas_call", "while/body/dot"))
                  if "/attn_linear/" in op else (n, o, us, op)
                  for n, o, us, op in LEAVES)
    run = _run(tmp_path, _hand(plain))
    assert _read(NEW_READER, run) is None           # and does not raise
    assert _read("step_kda_core_ms", run) == _ms(CORE)
    assert _read("step_attn_kernel_ms", run) == _ms(ATTN_KERNEL)


# -- the entries, every one found by its name --------------------------------------

def _lists(bench):
    return {m["name"]: m["workloads"]
            for m in bench["end_to_end"] + bench["per_layer"]
            if "workloads" in m}


def test_the_entries_are_appended_and_name_the_cell_on_every_list_it_reports():
    bench = spec.load_benchmark()
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config == {
        "name": CONFIG, "source": "https://huggingface.co/Qwen/"
        "Qwen3-Next-80B-A3B-Instruct/blob/main/config.json",
        "file": f"chipbench/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
        "why": config["why"]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "pretrain-1x16384", "chips": 1,
                    "why": cell["why"]}
    assert "16 x a share" in cell["why"] and "320 rows" in cell["why"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) > names.index("train-trinitymini-ep16share")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    lists = _lists(bench)
    # appended: behind every cell that was on a list before it
    for name, cells in lists.items():
        if CELL in cells:
            assert all(cells.index(CELL) > cells.index(c)
                       for c in (GPT2, KIMI, "train-trinitymini-ep16share")
                       if c in cells), name
    reports = {name for name, cells in lists.items() if CELL in cells}
    everywhere = {name for name, cells in lists.items() if GPT2 in cells}
    assert everywhere <= reports
    assert reports - everywhere - {"setup_s"} == {
        NEW_READER,
        # the expert layer
        "step_moe_experts_ms", "step_moe_route_ms", "step_moe_shared_ms",
        "moe_load_max", "moe_held_off_balance", "moe_full_buffer",
        # the one attention layer, by its parts (2 key heads under 16)
        "step_attn_full_ms", "attn_kernel_peak_share", "step_attn_pos_ms",
        "step_attn_gqa_ms", "step_attn_layout_ms", "step_attn_kernel_ms",
        "attn_outside_peak_share",
        # the linear mixer by scope, whatever implements it
        "step_attn_linear_ms", "step_kda_core_ms", "step_kda_conv_ms",
        "step_kda_gate_ms", "kda_core_peak_share"} - everywhere
    # not on: the accepted tests pin these lists to the cell they were
    # added for (PERF.md section 7), the balanced count can pass 100
    # (section 5), and this model has neither a window, a latent nor
    # collectives
    assert not {"step_kda_kernel_ms", "step_attn_gate_ms",
                "step_post_norm_ms", "moe_experts_peak_share",
                "step_attn_window_ms", "step_mla_latent_ms",
                "collective_exposed"} & reports
    new = next(m for m in bench["per_layer"] if m["name"] == NEW_READER)
    assert new == {"name": NEW_READER, "unit": "ms", "better": "lower",
                   "source": "device_trace", "layer": "model step",
                   "moves": "train_tok_s_chip", "workloads": [CELL]}
    per_layer = [m["name"] for m in bench["per_layer"]]
    assert per_layer.index(NEW_READER) > per_layer.index("step_post_norm_ms")
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"train_tok_s_chip", "setup_s"}


# -- the sums -----------------------------------------------------------------------

def test_the_files_sums_are_the_programs_the_flop_functions_and_a_hand_count():
    from chipbench.flops import qwen3_next as flops

    data, cfg = _config()
    p = data["parameters"]
    gdn = (2048 * 12288 + 2048 * 64 + 8192 * 4 + 64 + 128 + 4096 * 2048)
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 512
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048 + 32 * 3 * 2048 * 512
    assert (p["gated_deltanet_mixer"], p["attention_mixer"],
            p["expert_layer_ffn"]) == (gdn, attn, ffn) == (
        33_718_464, 27_263_488, 104_859_648)
    assert p["deltanet_layer"] == gdn + ffn + 2 * 2048 == 138_582_208
    assert p["attention_layer"] == attn + ffn + 2 * 2048 == 132_127_232
    period = 3 * p["deltanet_layer"] + p["attention_layer"]
    assert p["period_d_d_d_a"] == period == 547_873_856
    assert p["embedding_and_head"] == 2 * 18992 * 2048 == 77_791_232
    total = period + p["embedding_and_head"] + p["final_norm"]
    assert (p["total"] == total == cfg.num_params() == flops.n_params(cfg)
            == 625_667_136)
    assert p["bytes_at_16_a_parameter"] == 16 * total
    assert 0.25 < 16 * total / 16e9 < 0.7          # over the floor, with room
    assert "625,667,136" in data["deployment"]
    spec.load_part("sizes", "qwen3_next").check(data, cfg)
    # what a token passes through: the mixers' matrices, the whole router,
    # the shared expert with its gate, top-10 x 32 / 512 held experts at
    # balance, the head
    one_expert = 3 * 2048 * 512
    assert flops.held_share(cfg) == 1 / 16
    assert flops._gdn_params(cfg) == 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert flops._attention_params(cfg) == attn - 512
    assert flops.matmul_params(cfg) == pytest.approx(
        3 * flops._gdn_params(cfg) + flops._attention_params(cfg)
        + 4 * (2048 * 512 + one_expert + 2048 + 10 / 16 * one_expert)
        + 2048 * 18992)
    assert flops.expert_matmul_params(cfg) + flops.shared_matmul_params(
        cfg) + 4 * 2048 * 512 == pytest.approx(24.6e6, rel=5e-3)
    # the recurrence: three products with the 128 x 128 state a token and
    # VALUE head (32, not the 16 key heads)
    assert flops.kda_core_flops_per_token(cfg) == 3 * 32 * 6 * 128 * 128
    assert flops.kda_core_flops_per_step(cfg, 16384, 1) == (
        3 * 3 * 32 * 6 * 128 * 128 * 16384)
    pairs = 16384 * 16385 // 2
    assert flops.visible_pairs(16384) == pairs
    kernels = flops.attention_kernel_flops_per_step(cfg, 16384, 1)
    assert kernels == 14 * 16 * 256 * pairs
    assert flops.attention_flops_per_token(cfg, 16384) == pytest.approx(
        kernels * 2 / 7 / 16384 + flops.kda_core_flops_per_token(cfg))
    assert flops.train_flops_per_token(cfg, 16384) == pytest.approx(
        6 * flops.matmul_params(cfg)
        + 3 * flops.attention_flops_per_token(cfg, 16384))
    # ISSUE 51's reckoning: a step of some 25.6 TFLOP
    assert flops.train_flops_per_token(cfg, 16384) * 16384 == pytest.approx(
        25.6e12, rel=0.03)
    assert flops.experts_train_flops_per_token(cfg) == pytest.approx(
        6 * 4 * 10 / 16 * one_expert)
    assert kernels / flops.attention_kernel_bytes_per_step(cfg, 16384, 1) \
        > 197e12 / 819e9                            # compute-bound


@pytest.mark.parametrize("changes,named", [
    (dict(linear_key_heads=32), "linear_num_key_heads: the file states 16"),
    (dict(rope_fraction=1.0), "partial_rotary_factor: the file states 0.25"),
    (dict(norm_zero_centred=False),
     "zero_centred_norms: the file states True"),
    (dict(shared_expert_gate=False),
     "shared_expert_gate: the file states True"),
    (dict(attn_gate=False), "attention_gate: the file states True"),
    (dict(qk_norm=True), "qk_norm: the file states 'head'"),
    (dict(experts_held=(0, 32)), "num_experts: the file states 32"),
    (dict(expert_top_k=8), "num_experts_per_tok: the file states 10"),
    (dict(router_aux_weight=0.01),
     "router_aux_loss_coef: the file states 0.001"),
    (dict(n_kv_heads=4), "num_key_value_heads: the file states 2"),
    (dict(kda_conv=3), "linear_conv_kernel_dim: the file states 4"),
    (dict(rope_theta=1e6), "rope_theta: the file states 10000000"),
    (dict(max_seq_len=16384),
     "max_position_embeddings: the file states 262144"),
    (dict(layer_mixers=("gdn", "gdn", "attn", "gdn")),
     "full_attention_interval: the file states"),
])
def test_the_size_check_names_what_the_factory_runs_differently(changes,
                                                                named):
    data, cfg = _config()
    check = spec.load_part("sizes", "qwen3_next").check
    check(data, cfg)
    with pytest.raises(spec.SpecError, match=named):
        check(data, spec.model_config(data, **changes))


def test_the_config_file_states_the_catalogs_numbers_and_its_cuts():
    data, _ = _config()
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):                 # key by key, where it is
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert data["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if data.get(k) != v]
        assert differs == [] or sorted(differs) == sorted(data["reduced"])
        assert all(data["published"][k] == row["config"][k]
                   for k in data["reduced"])
    assert data["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert data["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                 "vocab_size": 151936}
    assert (data["num_hidden_layers"], data["num_experts"],
            data["vocab_size"]) == (4, 32, 18992)
    # no width differs from the source, and no other number
    assert (data["hidden_size"], data["head_dim"],
            data["num_attention_heads"], data["num_key_value_heads"],
            data["linear_num_key_heads"], data["linear_num_value_heads"],
            data["linear_key_head_dim"], data["linear_value_head_dim"],
            data["linear_conv_kernel_dim"], data["moe_intermediate_size"],
            data["shared_expert_intermediate_size"],
            data["num_experts_per_tok"], data["intermediate_size"],
            data["partial_rotary_factor"], data["rope_theta"],
            data["full_attention_interval"], data["rms_norm_eps"],
            data["max_position_embeddings"]) == (
        2048, 256, 16, 2, 16, 32, 128, 128, 4, 512, 512, 10, 5120, 0.25,
        10000000, 4, 1e-6, 262144)
    # the floors: a whole period of four, 8 experts, an eighth of the
    # vocabulary
    assert data["num_hidden_layers"] % data["full_attention_interval"] == 0
    assert data["num_experts"] * 16 == 512 and data["num_experts"] >= 8
    assert data["vocab_size"] * 8 == 151936
    assert "16 chips share each layer" in data["deployment"]
    assert data["assumed"]["router_width"] == 512
    assert data["assumed"]["router_aux_loss_coef"] == 0.001
    for key in ("deltanet_equations", "deltanet_init", "attention_gate_is",
                "zero_centred_norms_is", "partial_rotary_is",
                "shared_expert_gate_is", "learning_rate", "weights"):
        assert data["assumed"][key]
    assert any("multi-token-prediction" in d for d in data["departures"])
    assert data["optimizer"]["name"] == "adamw"
    assert "agreement_limits" not in data or data["agreement_limits"]["why"]
    cell = spec.load_cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-1x16384")
    traffic = cell["traffic_data"]
    assert (traffic["seq_len"], traffic["rows_per_chip"],
            traffic["fetch_every"], traffic["warmup_steps"],
            traffic["reference_rows"]) == (16384, 1, 4, 2, 1)


# -- the reference's controls at test size ----------------------------------------

@pytest.fixture(scope="module")
def tiny():
    """``reading(control, **broken)``: ``agreement``'s ``logit_rel_d`` at
    the tiny configuration in float32, two seeded rows, for the program
    with one side's weights wrong or under another configuration;
    ``reading.sound`` is the sound program's."""
    import jax
    import jax.numpy as jnp

    from chipbench import traffic_gen
    from chipbench.drivers import train_job
    from chipbench.reference import _common
    from ray_tpu import models

    with open(os.path.join(_tinycells.TINY, "tiny-qwen3-next.config.json")) \
            as f:
        data = json.load(f)
    cfg = spec.model_config(data, dtype="float32")
    params = jax.jit(lambda k: models.init_params(k, cfg))(
        jax.random.PRNGKey(51))
    rows = traffic_gen.token_rows(range(2), 51, 96, cfg.vocab_size)
    ref = spec.load_part("reference", "qwen3_next")

    def reading(control=None, **broken):
        call = train_job.program_side(
            spec.model_config(data, dtype="float32", **broken))
        mine, theirs = _common.apply_control(control, params)
        tokens = jnp.asarray(rows)
        return _common.agreement(
            ref, theirs, rows, cfg,
            lambda: call(mine, tokens, tokens)[:2])["logit_rel_d"]

    reading.sound = reading()
    return reading


def test_the_sound_program_reads_float32s_rounding(tiny):
    assert tiny.sound < 1e-4


@pytest.mark.parametrize("control,broken", [
    ("float8_weights", {}),
    ("drop_layer=0", {}),                       # a DeltaNet layer
    ("drop_layer=3", {}),                       # the attention layer
    ("drop_experts=4", {}),                     # the last layer's held experts
    (None, dict(shared_expert_gate=False)),     # the shared expert's gate
    (None, dict(rope_fraction=1.0)),            # the whole head rotated
    (None, dict(norm_zero_centred=False)),      # w = 0 read as the scale
    (None, dict(attn_gate=False)),
], ids=str)
def test_a_control_reads_far_outside_the_sound_program(tiny, control, broken):
    assert tiny(control, **broken) > 50 * max(tiny.sound, 1e-6)


# -- the cell at test size through JaxTrainer.fit ----------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with ``tiny-qwen3-next`` added: a config
    file of the ``qwen3_next`` arch at test size and the tiny traffic
    file; the arch's own reference, FLOP count, size check and readers are
    the repository's."""
    root = os.path.join(str(tmp_path_factory.mktemp("qwen3next")), "root")
    shutil.copytree(os.path.join(_tinycells.REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    name = "tiny-qwen3-next"
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


def test_the_tiny_cell_trains_through_jaxtrainer_and_reports_the_counters(
        root):
    """``JaxTrainer.fit`` -> ``ray_tpu.data`` -> ``make_train_step`` with
    the default step options, the program's logits and whole loss (the
    balance term in it) against the reference's (D D D A D, the delta rule
    token by token, 4 of 16 experts held), on fake chips; the three
    counters are among the last step's."""
    data = spec.load_json("chipbench", "configs", "tiny-qwen3-next.json",
                          root=root)
    spec.load_part("sizes", "qwen3_next").check(data, spec.model_config(data))
    code = (
        "import json\n"
        "from chipbench import run\n"
        "res = run.run_cell('tiny-qwen3-next', seed=3900000051, "
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
    assert "+ the rest 0.00000" not in note       # the balance term is there
    counters = next(n for n in res["notes"]
                    if n.startswith("the last step's counters:"))
    share = float(counters.split("moe_shared_gate_mean ")[1].split(",")[0])
    gate = float(counters.split("attn_gate_mean ")[1].split(",")[0])
    assert share == pytest.approx(0.5, abs=0.02)
    assert gate == pytest.approx(0.5, abs=0.02)
    for name in ("kda_log_decay_min -", "moe_held_share", "moe_full_buffer",
                 "router_aux"):
        assert name in counters
    assert all(f"check {name}: ok" in res["notes"] for name in (
        "program_agrees_with_reference", "first_step_is_the_compared_forward",
        "step_moves_the_weights", "step_compiled_once"))
