"""Attention by its parts: the eight per-layer readers over
``layer_metrics/_attn_parts.py`` on a hand-written trace whose answers are
computed by hand (sums, the kernel test, ``attn_pos`` nested inside
``mla_latent``, ``attn_rest``) and on one without the names, their
``BENCHMARK.json`` entries, and the projections' FLOPs against a count by
hand at SmallThinker's and kanana-2's widths. CPU only."""

from __future__ import annotations

import pytest

from chipbench import spec, xplane
from chipbench.flops import _attn_proj
from chipbench.layer_metrics import _attn_parts, _attn_scopes

CELL = "train-smallthinker-ep4share"
READERS = ("step_attn_qkv_ms", "step_attn_out_ms", "step_attn_core_ms",
           "step_attn_pos_ms", "step_attn_gqa_ms", "step_attn_layout_ms",
           "step_attn_kernel_ms", "attn_outside_peak_share")
FWD = "jit(train_step)/jvp(layers)/while/body/closed_call"
BWD = "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint"
A = "attn/attn_full"
# One device, TWO runs of the train step. (instruction, us, op_name):
LEAVES = (
    ("qkv.1", "fusion", 4, f"{FWD}/{A}/attn_qkv/dot_general:"),
    ("pos.2", "fusion", 1, f"{FWD}/{A}/attn_pos/mul:"),
    ("gqa.3", "fusion", 1, f"{FWD}/{A}/attn_gqa/broadcast_in_dim:"),
    ("tr.4", "copy", 2, f"{FWD}/{A}/attn_core/attn_layout/transpose:"),
    ("fwd.5", "custom-call", 6, f"{FWD}/{A}/attn_core/jvp()/pallas_call:"),
    ("out.6", "fusion", 2, f"{FWD}/{A}/attn_out/dot_general:"),
    ("qkv.7", "fusion", 3,
     f"{BWD}/rematted_computation/{A}/attn_qkv/dot_general:"),
    ("delta.8", "fusion", 1,
     f"{BWD}/{A}/attn_core/transpose(jvp())/attn_delta/reduce_sum:"),
    ("dq.9", "custom-call", 8,
     f"{BWD}/{A}/attn_core/transpose(jvp())/pallas_call:"),
    ("tr.10", "copy", 2,
     f"{BWD}/{A}/attn_core/transpose(jvp())/attn_layout/transpose:"),
    # the sum of the heads' rotary-key gradients: ``attn_core``'s own
    ("sum.11", "fusion", 1,
     f"{BWD}/{A}/attn_core/transpose(jvp())/reduce_sum:"),
    ("lat.12", "fusion", 3, f"{FWD}/{A}/mla_latent/dot_general:"),
    ("rope.13", "fusion", 1, f"{FWD}/{A}/mla_latent/attn_pos/mul:"),
    ("cast.14", "fusion", 2, f"{FWD}/{A}/convert_element_type:"),
    # a kernel, not attention's
    ("gmm.15", "custom-call", 5,
     f"{FWD}/moe/moe_experts/jit(gmm)/pallas_call:"),
    # the first of two names has no scope: the second counts
    ("out.16", "fusion", 3, "jit(train_step)/transpose(jvp())/reshape;"
     f"{BWD}/{A}/attn_out/dot_general:"),
)
# us over both runs, by hand
QKV, POS, NESTED_POS, GQA, LAYOUT, DELTA, KERNEL = 7, 2, 1, 1, 4, 1, 14
CORE, OUT, MLA, REST, ATTN = 20, 5, 4, 2, 40
RUNS = 2


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


def _run(tmp_path, text: str, cell: str = CELL) -> dict:
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True, exist_ok=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return {"trace": xplane.load(xplane.find_xplane(str(tmp_path))),
            "trace_dir": str(tmp_path), "notes": [],
            "cell": spec.load_cell(cell),
            "peaks": spec.load_peaks("TPU v5 lite")}


def _read(name: str, run: dict):
    return spec.load_part("layer_metrics", name).read(run)


def _ms(us: float) -> float:
    return pytest.approx(us * 1e-3 / RUNS)


def test_the_readers_on_the_hand_trace(tmp_path):
    run = _run(tmp_path, _hand())
    assert _read("step_attn_qkv_ms", run) == _ms(QKV)
    assert _read("step_attn_pos_ms", run) == _ms(POS)
    assert _read("step_attn_gqa_ms", run) == _ms(GQA)
    assert _read("step_attn_core_ms", run) == _ms(CORE)
    assert _read("step_attn_out_ms", run) == _ms(OUT)
    assert _read("step_attn_layout_ms", run) == _ms(LAYOUT + DELTA)
    assert _read("step_attn_kernel_ms", run) == _ms(KERNEL)
    # the readers that were there read what they read
    assert _read("step_attn_ms", run) == _ms(ATTN)
    assert _read("step_attn_full_ms", run) == _ms(ATTN)
    assert _read("step_mla_latent_ms", run) == _ms(MLA)
    assert _attn_scopes.kernel_step_ms(run) == _ms(KERNEL)
    # the split adds up: the rows that do not overlap, and the rest
    assert ATTN == QKV + POS + GQA + CORE + OUT + (MLA - NESTED_POS) + REST
    assert _attn_parts.step_ms(run, _attn_parts.REST) == _ms(REST)
    # SmallThinker's projections, forward + backward in four layers, over
    # ALL of attention outside the kernels and the peak
    work = 4 * 6 * (2 * 2560 * 3584 + 2 * 2560 * 512) * 16384
    assert _read("attn_outside_peak_share", run) == pytest.approx(
        100 * work / ((ATTN - KERNEL) * 1e-6 / RUNS) / 197e12)
    run["peaks"] = None                      # a CPU rehearsal: no share
    assert _read("attn_outside_peak_share", run) is None


def test_one_note_holds_the_whole_split_by_pass(tmp_path):
    run = _run(tmp_path, _hand())
    for name in READERS:
        _read(name, run)
    notes = [n for n in run["notes"] if n.startswith("attn parts:")]
    assert len(notes) == 1
    note = notes[0]
    # ms a step, forward / recompute / backward, then a row's OWN longest
    assert "attn 0.020; " in note
    assert "attn_qkv 0.004 (0.002 / 0.002 / 0.000) [qkv.1 fusion" in note
    assert "attn_core 0.010 (0.004 / 0.000 / 0.006) [sum.11 fusion" in note
    assert ("pallas_call 0.007 (0.003 / 0.000 / 0.004) [dq.9 custom-call"
            in note)
    assert "attn_layout 0.002 (0.001 / 0.000 / 0.001) [tr.4 copy" in note
    assert "attn_pos 0.001 (0.001 / 0.000 / 0.000) [pos.2 fusion" in note
    assert "[rope.13 fusion" in note
    assert "attn_rest 0.001 (0.001 / 0.000 / 0.000) [cast.14 fusion" in note
    assert "gmm.15" not in note


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_names_reads_none(name, tmp_path):
    assert _read(name, {"trace": None, "trace_dir": None, "notes": []}) is None
    parent = tuple(
        (n, o, us, "/".join(p for p in op.split("/")
                            if p not in _attn_parts.NAMES))
        for n, o, us, op in LEAVES)
    run = _run(tmp_path, _hand(parent))
    assert _read(name, run) is None
    assert any("none of the seven names" in n for n in run["notes"])
    # what the parent's line has, it keeps
    assert _read("step_attn_ms", run) == _ms(ATTN)
    assert _attn_scopes.kernel_step_ms(run) == _ms(KERNEL)


def test_a_name_a_fusion_swallowed_reads_zero_not_none(tmp_path):
    """The program has the scopes, no instruction of this model kept
    ``attn_gqa``: its cost is not separable, which is a reading."""
    run = _run(tmp_path, _hand(tuple(
        leaf for leaf in LEAVES if "attn_gqa" not in leaf[3])))
    assert _read("step_attn_gqa_ms", run) == 0.0
    assert _read("step_attn_qkv_ms", run) == _ms(QKV)


@pytest.mark.parametrize("op_name,want", [
    (f"{FWD}/attn/attn_core/attn_layout/transpose:",
     (["attn", "attn_core", "attn_layout"], "attn_layout", "forward")),
    (f"{BWD}/attn/attn_window/attn_core/transpose(jvp())/pallas_call:",
     (["attn", "attn_core", "pallas_call"], "pallas_call", "backward")),
    (f"{BWD}/rematted_computation/attn/attn_full/mla_latent/attn_pos/mul:",
     (["attn", "mla_latent", "attn_pos"], "attn_pos", "recompute")),
    (f"{FWD}/attn/add:", (["attn", "attn_rest"], "attn_rest", "forward")),
    (f"{FWD}/moe/moe_experts/jit(gmm)/pallas_call:", None),
    ("", None),
])
def test_which_rows_an_instruction_counts_under(op_name, want):
    found = _attn_parts.names_of(op_name)
    assert found == want


def _entries():
    return [m for m in spec.load_benchmark()["per_layer"]
            if m["name"] in READERS]


def test_every_reader_has_its_entry_and_its_file():
    assert [m["name"] for m in _entries()] == list(READERS)
    cells = {w["name"] for w in spec.load_benchmark()["workloads"]}
    for m in _entries():
        assert callable(spec.load_part("layer_metrics", m["name"]).read)
        assert set(m["workloads"]) <= cells, m
        assert (m["layer"], m["source"], m["moves"]) == (
            "model step", "device_trace", "train_tok_s_chip")
        assert m["unit"] == ("%" if m["name"].endswith("share") else "ms")


def _has_the_work(metric: str, cell: dict) -> bool:
    """From a cell's own configuration and row length: positions where
    the arch rotates or norms q and k, the repeat where there are fewer
    key heads than query heads, the kernels' three above 1,024 keys,
    projections and a core everywhere."""
    cfg = spec.model_config(cell["config_data"])
    if metric == "step_attn_pos_ms":
        return cfg.arch != "gpt2"
    if metric == "step_attn_gqa_ms":
        return cfg.kv_heads != cfg.n_heads
    if metric in ("step_attn_qkv_ms", "step_attn_out_ms",
                  "step_attn_core_ms"):
        return True
    return cell["traffic_data"]["seq_len"] > 1024


@pytest.mark.parametrize("entry", _entries(), ids=lambda m: m["name"])
def test_an_entry_lists_the_cells_whose_model_has_the_work(entry):
    cells = [w["name"] for w in spec.load_benchmark()["workloads"]]
    want = [c for c in cells
            if _has_the_work(entry["name"], spec.load_cell(c))]
    assert sorted(entry["workloads"]) == sorted(want)


def test_the_projections_flops_against_a_count_by_hand():
    st = spec.model_config(spec.load_cell(CELL)["config_data"])
    layer = 2560 * 28 * 128 + 2 * 2560 * 4 * 128 + 28 * 128 * 2560
    assert _attn_proj.projection_params(st) == layer == 20_971_520
    step = _attn_proj.train_flops_per_step(st, 16384, 1)
    assert step == pytest.approx(4 * 6 * layer * 16384)
    # ISSUE 37's 2.45 TFLOP a layer holds q, k, v a second time (the
    # recompute), which the share leaves out: 2.06 without
    into = layer - 28 * 128 * 2560
    assert (6 * layer + 2 * into) * 16384 == pytest.approx(2.45e12, rel=2e-3)
    assert step / 4 == pytest.approx(2.06e12, rel=2e-3)
    k2 = spec.model_config(
        spec.load_cell("train-kanana2-ep8share")["config_data"])
    layer = (2048 * 32 * 192 + 2048 * (512 + 64) + 512 * 32 * (128 + 128)
             + 32 * 128 * 2048)
    assert _attn_proj.projection_params(k2) == layer == 26_345_472
    assert _attn_proj.train_flops_per_step(k2, 8192, 2) == pytest.approx(
        5 * 6 * layer * 16384)
    assert 5 * 6 * layer * 16384 == pytest.approx(12.9e12, rel=5e-3)
