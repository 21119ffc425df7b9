"""What the ``kimi_linear`` configuration brings to the benchmark: its
five per-layer readers on a hand-written trace whose answers are computed
by hand (and with nothing to read: the parent's program, no trace), the
readers that are there on this arch's instructions, its FLOP functions
against the program's own parameter count and the recurrence's count by
hand, its size check, its config file against the source's numbers, and
its cell at test size through the real entry point (``JaxTrainer.fit`` on
fake chips), added to a temporary copy of the benchmark the way a later
PR adds a cell. CPU only; the cell itself is rehearsed at its real size
by ``test_chipbench_rehearsal.py`` and held to the contract by
``test_chipbench_spec.py``, which pick it up by name."""

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

CELL = "train-kimilinear-ep32share"
CONFIG = "kimi-linear-48b-a3b-ep32.json"
NEW_READERS = ("step_attn_linear_ms", "step_kda_core_ms", "step_kda_conv_ms",
               "step_kda_gate_ms", "kda_core_peak_share")
FWD = "jit(train_step)/jvp(layers)/closed_call"
BWD = "jit(train_step)/transpose(jvp(layers))/closed_call/checkpoint"
# One device, two runs of the train step in 20 us. Leaves (us):
#   fusion.1  0-3   attn_linear / attn_qkv: a KDA layer's q projection
#   fusion.2  3-5   attn_linear / kda_conv forward
#   fusion.3  5-6   attn_linear / kda_gate, recomputed
#   fusion.4  6-10  attn_linear / attn_core: the delta rule's chunk scan
#   fusion.5 10-13  attn_linear / attn_core, backward
#   fwd.6    13-15  attn_full / attn_core: the latent layer's Pallas kernel
#   fusion.7 15-16  attn_linear / attn_out
#   fusion.8 16-18  attn_full / mla_latent
#   fusion.9 18-20  moe / moe_shared
# attn_linear 3+2+1+4+3+1 = 14 us, of it the delta rule 7, kda_conv 2,
# kda_gate 1; attn_full 2+2 = 4; attn 18; two runs.
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 4000000 }
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 3000000 }
    events { metadata_id: 6 offset_ps: 13000000 duration_ps: 2000000 }
    events { metadata_id: 7 offset_ps: 15000000 duration_ps: 1000000 }
    events { metadata_id: 8 offset_ps: 16000000 duration_ps: 2000000 }
    events { metadata_id: 9 offset_ps: 18000000 duration_ps: 2000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 10 offset_ps: 10000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_linear/attn_qkv/btd,dhk->bthk/dot_general:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %b)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_linear/kda_conv/mul:" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %c)"
    stats { metadata_id: 1 str_value: "BWD/rematted_computation/attn/attn_linear/kda_gate/exp:" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %d)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_linear/attn_core/jvp()/while/body/while/body/dot_general:" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = bf16[8]{0} fusion(bf16[8]{0} %e)"
    stats { metadata_id: 1 str_value: "BWD/attn/attn_linear/attn_core/transpose(jvp())/while/body/dot_general:" } } }
  event_metadata { key: 6 value { id: 6 name: "%fwd.6 = bf16[8]{0} custom-call(bf16[8]{0} %f)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_full/attn_core/jvp()/pallas_call:" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %g)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_linear/attn_out/bthk,hkd->btd/dot_general:" } } }
  event_metadata { key: 8 value { id: 8 name: "%fusion.8 = bf16[8]{0} fusion(bf16[8]{0} %h)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_full/mla_latent/dot_general:" } } }
  event_metadata { key: 9 value { id: 9 name: "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %i)"
    stats { metadata_id: 1 str_value: "FWD/moe/moe_shared/dot_general:" } } }
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
    return {"trace": xplane.load(xplane.find_xplane(trace_dir)),
            "trace_dir": trace_dir, "notes": [], "cell": spec.load_cell(CELL),
            "train": {"tokens_per_step": 16384},
            "peaks": spec.load_peaks("TPU v5 lite")}


def _read(name: str, run: dict):
    return spec.load_part("layer_metrics", name).read(run)


def test_the_new_readers_on_the_hand_trace(tmp_path):
    run = _run(_write(tmp_path, HAND))
    assert _read("step_attn_linear_ms", run) == pytest.approx(7.0e-3)
    assert _read("step_kda_core_ms", run) == pytest.approx(3.5e-3)
    assert _read("step_kda_conv_ms", run) == pytest.approx(1.0e-3)
    assert _read("step_kda_gate_ms", run) == pytest.approx(0.5e-3)
    # the readers that were there: the two kinds of layer add up to attn;
    # attn_core holds the delta rule AND the latent layer's kernel
    assert _read("step_attn_full_ms", run) == pytest.approx(2.0e-3)
    assert _read("step_attn_ms", run) == pytest.approx(9.0e-3)
    assert _read("step_attn_core_ms", run) == pytest.approx(4.5e-3)
    assert _read("step_attn_kernel_ms", run) == pytest.approx(1.0e-3)
    assert _read("step_attn_qkv_ms", run) == pytest.approx(1.5e-3)
    assert _read("step_attn_pos_ms", run) == 0.0        # nothing is rotated
    # ``flops/_attn_proj.py`` counts a LATENT layer's projections for all
    # five layers (it does not know KDA's): 8 us a step outside the kernel
    latent = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    assert _read("attn_outside_peak_share", run) == pytest.approx(
        100 * 5 * 6 * latent * 16384 / 8.0e-6 / 197e12)
    assert _read("step_mla_latent_ms", run) == pytest.approx(1.0e-3)
    assert _read("step_moe_shared_ms", run) == pytest.approx(1.0e-3)
    # 3.5 us of delta rule a step against the recurrence's count: four KDA
    # layers, 32 heads, 16,384 tokens, 6 x 128 x 128 forward + twice that
    assert _read("kda_core_peak_share", run) == pytest.approx(
        100 * 4 * 32 * 16384 * 18 * 128 * 128 / 3.5e-6 / 197e12)
    # 1 us of kernel a step against ONE latent layer's visible pairs
    pairs = 16384 * 16385 // 2
    assert _read("attn_kernel_peak_share", run) == pytest.approx(
        100 * 1 * 32 * 2304 * pairs / 1.0e-6 / 197e12)
    assert any(n.startswith("scope attn_linear + attn_core:")
               for n in run["notes"])


@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_return_none_with_nothing_to_read(name, tmp_path):
    assert _read(name, {"trace": None, "trace_dir": None, "notes": []}) is None
    # a program without the new scopes (the parent's): nothing to read
    plain = HAND.replace("/attn_linear/", "/attn_full/").replace(
        "/kda_conv/", "/").replace("/kda_gate/", "/")
    run = _run(_write(tmp_path, plain))
    assert _read(name, run) is None
    assert _read("step_attn_ms", run) == pytest.approx(9.0e-3)


def test_the_new_readers_are_declared_for_this_cell_alone():
    bench = spec.load_benchmark()
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == list(NEW_READERS)
    assert bench["per_layer"][-5:] == new
    for m in new:
        assert (m["workloads"], m["layer"], m["moves"], m["source"]) == (
            [CELL], "model step", "train_tok_s_chip", "device_trace")
        assert m["better"] == ("higher" if "share" in m["name"] else "lower")
    reports = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert {"step_attn_full_ms", "step_mla_latent_ms", "step_attn_kernel_ms",
            "attn_kernel_peak_share", "step_moe_shared_ms", "moe_full_buffer",
            "moe_held_off_balance", "step_attn_core_ms", "mfu"} <= reports
    # the accepted ``test_chipbench_attn_parts.py`` wants a cell of an
    # arch that is not gpt2 on ``step_attn_pos_ms`` (it reads 0.0 here:
    # nothing is rotated) and one above 1,024 positions on
    # ``attn_outside_peak_share`` (``flops/_attn_proj.py`` counts a latent
    # layer's projections for every layer: an undercount, so it reads low)
    assert {"step_attn_pos_ms", "attn_outside_peak_share"} <= reports
    assert not {"moe_experts_peak_share", "step_attn_gqa_ms",
                "step_attn_window_ms"} & reports
    assert len(reports) == 18 + 13 + 5


def test_kimi_linear_flop_functions_count_the_programs_parameters():
    from chipbench.flops import kimi_linear as flops

    data = spec.load_json("chipbench", "configs", CONFIG)
    cfg = spec.model_config(data)
    assert flops.n_params(cfg) == cfg.num_params() == 602_434_432
    shapes = cfg.shapes()
    size = lambda tree, skip=(): sum(
        int(np.prod(s.shape[1:])) for name, s in tree.items()
        if name not in skip)
    wo = 4096 * 2304
    kda = size(shapes["layers"]["kda"],
               ("conv_q", "conv_k", "conv_v", "dt_bias", "A_log", "o_norm"))
    assert kda + wo == flops._kda_params(cfg) == (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    assert size(shapes["layers"]["kda"]) - kda == flops._kda_leaves(cfg) == (
        3 * 4 * 4096 + 4096 + 32 + 128)
    latent = size(shapes["layers"]["mla"], ("kv_norm",)) + wo
    assert latent == 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + wo
    one_expert, dense = 3 * 2304 * 1024, 3 * 2304 * 9216
    assert flops.held_share(cfg) == 1 / 32
    assert flops.matmul_params(cfg) == pytest.approx(
        4 * (kda + wo) + latent
        + 4 * (2304 * 256 + 8 / 32 * one_expert + one_expert) + dense
        + 2304 * 20480)
    # the recurrence: three products with the 128 x 128 state a token and
    # head, forward; twice that backward
    assert flops.kda_core_flops_per_token(cfg) == 4 * 32 * 6 * 128 * 128
    assert flops.kda_core_flops_per_step(cfg, 16384, 1) == pytest.approx(
        4 * 0.1546e12, rel=1e-3)
    pairs = flops.visible_pairs(16384)
    assert flops.attention_flops_per_token(cfg, 16384) == pytest.approx(
        2 * 32 * (192 + 128) * pairs / 16384 + 4 * 32 * 6 * 128 * 128)
    assert flops.train_flops_per_token(cfg, 16384) == pytest.approx(
        6 * flops.matmul_params(cfg)
        + 3 * flops.attention_flops_per_token(cfg, 16384))
    assert 41e12 < flops.train_flops_per_token(cfg, 16384) * 16384 < 43e12
    kernels = flops.attention_kernel_flops_per_step(cfg, 16384, 1)
    assert kernels == pytest.approx(
        32 * (2 * (192 + 128) + 2 * (3 * 192 + 2 * 128)) * pairs)
    assert kernels / flops.attention_kernel_bytes_per_step(cfg, 16384, 1) \
        > 197e12 / 819e9


@pytest.mark.parametrize("changes,named", [
    (dict(kv_latent=256), "kv_lora_rank: the file states 512"),
    (dict(latent_rope=True), "mla_use_nope: the file states True"),
    (dict(kda_conv=3), "short_conv_kernel_size: the file states 4"),
    (dict(layer_mixers=("kda", "kda", "attn", "kda", "kda")),
     "kda_layers up to num_hidden_layers: the file states \\[1, 2, 3, 5\\]"),
    (dict(experts_held=(0, 16)), "num_experts: the file states 8"),
    (dict(d_ff_dense=4096), "intermediate_size: the file states 9216"),
    (dict(d_ff_shared=2048), "num_shared_experts x moe_intermediate_size: "
                             "the file states 1024"),
    (dict(router_score="softmax"),
     "moe_router_activation_func: the file states 'sigmoid'"),
    (dict(expert_gate_scale=1.0),
     "routed_scaling_factor: the file states 2.446"),
    (dict(expert_top_k=6), "num_experts_per_token: the file states 8"),
    (dict(norm_eps=1e-6), "rms_norm_eps: the file states 1e-05"),
    (dict(router_bias_rate=0.01), "router_bias_rate: the file states 0.001"),
    (dict(max_seq_len=16384), "model_max_length: the file states 1048576"),
])
def test_kimi_linear_size_check_names_what_the_factory_runs_differently(
        changes, named):
    data = spec.load_json("chipbench", "configs", CONFIG)
    check = spec.load_part("sizes", "kimi_linear").check
    check(data, spec.model_config(data))
    with pytest.raises(spec.SpecError, match=named):
        check(data, spec.model_config(data, **changes))


def test_the_config_file_states_the_sources_numbers_and_its_cuts():
    data = spec.load_json("chipbench", "configs", CONFIG)
    assert data["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert data["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                 "vocab_size": 163840}
    assert (data["num_hidden_layers"], data["num_experts"],
            data["vocab_size"]) == (5, 8, 20480)
    # no width differs from the source
    assert (data["hidden_size"], data["intermediate_size"],
            data["moe_intermediate_size"], data["kv_lora_rank"],
            data["q_lora_rank"], data["qk_nope_head_dim"],
            data["qk_rope_head_dim"], data["v_head_dim"], data["head_dim"],
            data["num_attention_heads"], data["num_key_value_heads"],
            data["num_experts_per_token"], data["num_shared_experts"],
            data["routed_scaling_factor"], data["rope_theta"],
            data["model_max_length"], data["rms_norm_eps"]) == (
        2304, 9216, 1024, 512, None, 128, 64, 128, 72, 32, 32, 8, 1, 2.446,
        10000, 1048576, 1e-5)
    assert data["linear_attn_config"] == {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4}
    assert (data["model_type"], data["mla_use_nope"],
            data["moe_router_activation_func"], data["moe_renormalize"],
            data["first_k_dense_replace"], data["use_grouped_topk"],
            data["num_expert_group"], data["topk_group"]) == (
        "kimi_linear", True, "sigmoid", True, 1, True, 1, 1)
    assert data["vocab_size"] * 8 == 163840
    assert data["num_experts"] * 32 == 256
    assert "32 chips share each layer" in data["deployment"]
    assert "602.4 M params" in data["deployment"]
    for key in ("kda_equations", "kda_init", "kda_low_rank_is",
                "router_bias_rule", "mla_shared_part", "loss"):
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
    """A copy of the benchmark with ``tiny-kimi-linear`` added: a config
    file of the ``kimi_linear`` arch at test size and the tiny traffic
    file; the arch's own reference, FLOP count, size check and readers are
    the repository's."""
    root = os.path.join(str(tmp_path_factory.mktemp("kimi")), "root")
    shutil.copytree(os.path.join(_tinycells.REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    name = "tiny-kimi-linear"
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


def test_the_tiny_cell_trains_through_jaxtrainer_and_is_correct(root):
    """``JaxTrainer.fit`` -> ``ray_tpu.data`` -> ``make_train_step`` with
    the default step options, the program's logits against the
    reference's token by token (KDA layers by the recurrence, the NoPE
    latent layer, 4 of 8 experts held), on fake chips."""
    data = spec.load_json("chipbench", "configs", "tiny-kimi-linear.json",
                          root=root)
    spec.load_part("sizes", "kimi_linear").check(data, spec.model_config(data))
    code = (
        "import json\n"
        "from chipbench import run\n"
        "res = run.run_cell('tiny-kimi-linear', seed=3900000023, "
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
    for name in ("kda_log_decay_min -", "router_bias_absmax",
                 "moe_held_share", "moe_full_buffer"):
        assert name in counters
    assert all(f"check {name}: ok" in res["notes"] for name in (
        "program_agrees_with_reference", "first_step_is_the_compared_forward",
        "step_moves_the_weights", "step_compiled_once"))
