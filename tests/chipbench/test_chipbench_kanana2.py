"""What the ``kanana2`` configuration brings to the benchmark: its two
per-layer readers on a hand-written trace whose answers are computed by
hand (and with nothing to read), the attention readers that are there on
this arch's instructions, its FLOP functions against the program's own
parameter count, its size check, the comparison that decides ``correct``
reading a program whose gates carry the router's bias OUTSIDE its limit,
and its cell at test size through the real entry point
(``JaxTrainer.fit`` on fake chips), sound and with the gate scale broken,
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

import numpy as np
import pytest

import _tinycells
from chipbench import spec, xplane

CELL = "train-kanana2-ep8share"
CONFIG = "kanana-2-30b-a3b-ep8.json"
NEW_READERS = ("step_mla_latent_ms", "step_moe_shared_ms")
FWD = "jit(train_step)/jvp(layers)/closed_call"
BWD = "jit(train_step)/transpose(jvp(layers))/closed_call/checkpoint"
# One device, two runs of the train step in 20 us. Leaves (us):
#   fwd.1     0-3    attn_full kernel forward (NOT under mla_latent)
#   fusion.2  3-5    mla_latent: the up-projection, forward
#   fusion.3  5-6    mla_latent: RoPE on the rotary key, recompute
#   fusion.4  6-9    mla_latent: the down-projection's gradient, backward
#   dq.5      9-13   attn_full kernel backward
#   fusion.6 13-15   moe_shared forward        fusion.7 15-18  moe_shared backward
#   gmm.8    18-19   moe_experts (not the shared expert's)
#   fusion.9 19-20   attn_full output projection (no kernel, no latent)
# mla_latent 2+1+3 = 6 us, moe_shared 2+3 = 5 us, attn_full 3+6+4+1 = 14 us,
# kernels 3+4 = 7 us; two runs.
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 9000000 duration_ps: 4000000 }
    events { metadata_id: 6 offset_ps: 13000000 duration_ps: 2000000 }
    events { metadata_id: 7 offset_ps: 15000000 duration_ps: 3000000 }
    events { metadata_id: 8 offset_ps: 18000000 duration_ps: 1000000 }
    events { metadata_id: 9 offset_ps: 19000000 duration_ps: 1000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 10 offset_ps: 10000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fwd.1 = bf16[8]{0} custom-call(bf16[8]{0} %a)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_full/jvp()/pallas_call:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %b)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_full/mla_latent/btc,chk->bthk/dot_general:" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %c)"
    stats { metadata_id: 1 str_value: "BWD/rematted_computation/attn/attn_full/mla_latent/mul:" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %d)"
    stats { metadata_id: 1 str_value: "jit(train_step)/transpose(jvp())/reshape;BWD/attn/attn_full/mla_latent/dot_general:" } } }
  event_metadata { key: 5 value { id: 5 name: "%dq.5 = bf16[8]{0} custom-call(bf16[8]{0} %e)"
    stats { metadata_id: 1 str_value: "BWD/attn/attn_full/transpose(jvp())/pallas_call:" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = bf16[8]{0} fusion(bf16[8]{0} %f)"
    stats { metadata_id: 1 str_value: "FWD/moe/moe_shared/dot_general:" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %g)"
    stats { metadata_id: 1 str_value: "BWD/moe/moe_shared/dot_general:" } } }
  event_metadata { key: 8 value { id: 8 name: "%gmm.8 = bf16[8]{0} custom-call(bf16[8]{0} %h)"
    stats { metadata_id: 1 str_value: "FWD/moe/moe_experts/jit(gmm)/pallas_call:" } } }
  event_metadata { key: 9 value { id: 9 name: "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %i)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_full/bthk,hkd->btd/dot_general:" } } }
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


def test_the_new_readers_on_the_hand_trace(tmp_path):
    run = _run(_write(tmp_path, HAND))
    assert _read("step_mla_latent_ms", run) == pytest.approx(3.0e-3)
    assert _read("step_moe_shared_ms", run) == pytest.approx(2.5e-3)
    # the readers that were there: all of attention is ``attn_full`` (and
    # ``attn``), the shared expert is in ``moe`` and not in its experts
    assert _read("step_attn_full_ms", run) == pytest.approx(7.0e-3)
    assert _read("step_attn_ms", run) == pytest.approx(7.0e-3)
    assert _read("step_mlp_ms", run) == pytest.approx(3.0e-3)
    assert _read("step_moe_experts_ms", run) == pytest.approx(0.5e-3)
    # 3.5 us of kernels a step against this arch's own FLOP function: five
    # layers, two rows, 32 heads, 2 x (192 + 128) + 2 x (3 x 192 + 2 x 128)
    pairs = 8192 * 8193 // 2
    assert _read("attn_kernel_peak_share", run) == pytest.approx(
        100 * 5 * 2 * 32 * 2304 * pairs / 3.5e-6 / 197e12)
    assert any(n.startswith("scope mla_latent:") for n in run["notes"])
    assert any(n.startswith("scope moe_shared:") for n in run["notes"])


@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_return_none_with_nothing_to_read(name, tmp_path):
    assert _read(name, {"trace": None, "trace_dir": None, "notes": []}) is None
    # the parent's program: neither scope on any instruction
    plain = HAND.replace("/mla_latent/", "/").replace("/moe_shared/", "/")
    run = _run(_write(tmp_path, plain))
    assert _read(name, run) is None
    assert _read("step_attn_full_ms", run) == pytest.approx(7.0e-3)


def test_kanana2_flop_functions_count_the_programs_parameters():
    from chipbench.flops import kanana2 as flops

    data = spec.load_json("chipbench", "configs", CONFIG)
    cfg = spec.model_config(data)
    assert flops.n_params(cfg) == cfg.num_params() == 575_955_968
    shapes = cfg.shapes()
    attn = sum(int(np.prod(s.shape[1:])) for name, s in
               shapes["layers"]["attn"].items() if name != "kv_norm")
    assert attn == 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048
    mlp = shapes["layers"]["mlp"]
    one_expert = sum(int(np.prod(mlp[n].shape[2:]))
                     for n in ("w_gate", "w_up", "w_down"))
    shared = sum(int(np.prod(mlp[f"shared_{n}"].shape[1:]))
                 for n in ("w_gate", "w_up", "w_down"))
    dense = sum(int(np.prod(s.shape[1:]))
                for s in shapes["dense_layers"]["mlp"].values())
    assert (one_expert, shared, dense) == (3 * 2048 * 768, 3 * 2048 * 1536,
                                           3 * 2048 * 6144)
    assert flops.held_share(cfg) == 0.125
    assert flops.matmul_params(cfg) == pytest.approx(
        5 * attn + 4 * (2048 * 128 + 6 * 0.125 * one_expert + shared) + dense
        + 2048 * 16032)
    assert flops.experts_train_flops_per_token(cfg) == pytest.approx(
        6 * 4 * 0.75 * one_expert)
    pairs = flops.visible_pairs(8192)
    i, j = np.arange(64)[:, None], np.arange(64)[None, :]
    assert flops.visible_pairs(64) == int((j <= i).sum())
    assert flops.attention_flops_per_token(cfg, 8192) == pytest.approx(
        5 * 2 * 32 * (192 + 128) * pairs / 8192)
    assert flops.train_flops_per_token(cfg, 8192) == pytest.approx(
        6 * flops.matmul_params(cfg)
        + 3 * flops.attention_flops_per_token(cfg, 8192))
    # about 46 TFLOP of model work a step of 16,384 tokens, 24.7 of them
    # in the attention kernels
    assert 45e12 < flops.train_flops_per_token(cfg, 8192) * 16384 < 47e12
    kernels = flops.attention_kernel_flops_per_step(cfg, 8192, 2)
    assert kernels == pytest.approx(
        5 * 2 * 32 * (2 * (192 + 128) + 2 * (3 * 192 + 2 * 128)) * pairs)
    assert 24.5e12 < kernels < 25e12
    # compute is the kernels' bound on this chip (peak FLOP per peak byte)
    assert kernels / flops.attention_kernel_bytes_per_step(cfg, 8192, 2) \
        > 197e12 / 819e9


@pytest.mark.parametrize("changes,named", [
    (dict(kv_latent=256), "kv_lora_rank: the file states 512"),
    (dict(d_head_rope=32), "qk_rope_head_dim: the file states 64"),
    (dict(d_head_v=64), "v_head_dim: the file states 128"),
    (dict(experts_held=(0, 4)), "n_routed_experts: the file states 16"),
    (dict(n_dense_layers=2), "first_k_dense_replace: the file states 1"),
    (dict(d_ff_dense=4096), "intermediate_size: the file states 6144"),
    (dict(d_ff_shared=768), "n_shared_experts x moe_intermediate_size: the "
                            "file states 1536"),
    (dict(router_score="softmax"), "scoring_func: the file states 'sigmoid'"),
    (dict(router_bias=False, router_bias_rate=0.0),
     "topk_method: the file states 'noaux_tc'"),
    (dict(expert_gate_scale=1.0), "routed_scaling_factor: the file states 2.448"),
    (dict(router_bias_rate=0.01), "router_bias_rate: the file states 0.001"),
    (dict(expert_top_k=8), "num_experts_per_tok: the file states 6"),
    (dict(norm_eps=1e-5), "rms_norm_eps: the file states 1e-06"),
    (dict(router_aux_weight=0.01), "router_aux_loss_coef: the file states 0.0"),
    (dict(max_seq_len=8192), "max_position_embeddings: the file states 32768"),
])
def test_kanana2_size_check_names_what_the_factory_runs_differently(
        changes, named):
    data = spec.load_json("chipbench", "configs", CONFIG)
    check = spec.load_part("sizes", "kanana2").check
    check(data, spec.model_config(data))
    with pytest.raises(spec.SpecError, match=named):
        check(data, spec.model_config(data, **changes))


def test_the_config_file_states_the_catalogs_numbers_and_its_cuts():
    data = spec.load_json("chipbench", "configs", CONFIG)
    assert data["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert data["published"] == {"num_hidden_layers": 48,
                                 "n_routed_experts": 128, "vocab_size": 128256}
    assert (data["num_hidden_layers"], data["n_routed_experts"],
            data["vocab_size"]) == (5, 16, 16032)
    # no width differs from the source
    assert (data["hidden_size"], data["intermediate_size"],
            data["moe_intermediate_size"], data["kv_lora_rank"],
            data["q_lora_rank"], data["qk_nope_head_dim"],
            data["qk_rope_head_dim"], data["qk_head_dim"], data["v_head_dim"],
            data["head_dim"], data["num_attention_heads"],
            data["num_key_value_heads"], data["num_experts_per_tok"],
            data["n_shared_experts"], data["routed_scaling_factor"],
            data["rope_theta"], data["max_position_embeddings"]) == (
        2048, 6144, 768, 512, None, 128, 64, 192, 128, 64, 32, 32, 6, 2, 2.448,
        1000000, 32768)
    assert (data["model_type"], data["scoring_func"], data["topk_method"],
            data["n_group"], data["topk_group"], data["first_k_dense_replace"],
            data["rope_interleave"]) == ("deepseek_v3", "sigmoid", "noaux_tc",
                                         1, 1, 1, True)
    assert data["vocab_size"] * 8 == 128256
    assert "eight chips share each layer" in data["deployment"]
    for key in ("router_bias_rule", "router_bias_init", "rope_pairing",
                "loss"):
        assert data["assumed"][key]
    assert "agreement_limits" not in data or data["agreement_limits"]["why"]
    traffic = spec.load_cell(CELL)["traffic_data"]
    assert (traffic["seq_len"], traffic["rows_per_chip"],
            traffic["fetch_every"], traffic["warmup_steps"],
            traffic["reference_rows"]) == (8192, 2, 4, 2, 1)
    bench = spec.load_benchmark()
    reports = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert {"step_mla_latent_ms", "step_moe_shared_ms", "step_attn_full_ms",
            "attn_kernel_peak_share", "step_moe_experts_ms",
            "step_moe_route_ms", "moe_experts_peak_share", "moe_load_max",
            "moe_held_off_balance", "mfu"} <= reports
    assert "step_attn_window_ms" not in reports


# -- the comparison that decides ``correct`` ---------------------------------------

def test_agreement_reads_the_bias_in_the_gates_outside_its_limit(monkeypatch):
    """A program whose gates are the BIASED scores of the chosen experts
    (the fault a seeded, non-zero bias is there to show) against the
    reference, through ``_common.agreement`` at the tiny size: outside
    the limit that decides ``correct``; the sound program well inside."""
    import jax
    import jax.numpy as jnp

    from chipbench.drivers import train_job
    from chipbench.reference import _common
    from ray_tpu import models
    from ray_tpu.ops import moe

    data = spec.load_json(os.path.join(_tinycells.TINY,
                                       "tiny-kanana2.config.json"), root="/")
    cfg = spec.model_config(data)
    ref = spec.load_part("reference", "kanana2")
    params = models.init_params(jax.random.PRNGKey(11), cfg)
    # a bias large enough to change the choice of many tokens and to weigh
    # in a gate: 50 x its N(0, 0.02) init (scores lie in 0.4-0.6)
    # and routed experts that weigh as much in the stream as the cell's do
    # at its widths (4 x their down matrices)
    router = dict(params["layers"]["router"])
    router["b"] = router["b"] * 50.0
    mlp = dict(params["layers"]["mlp"])
    mlp["w_down"] = mlp["w_down"] * 4.0
    params = dict(params, layers=dict(params["layers"], router=router,
                                      mlp=mlp))
    rows = np.asarray(jax.random.randint(jax.random.PRNGKey(12), (2, 33), 0,
                                         cfg.vocab_size))

    def stats():
        call = train_job.program_side(cfg)
        return _common.agreement(ref, params, rows, cfg, lambda: call(
            params, jnp.asarray(rows), jnp.asarray(rows))[:2])

    limits = _common.limits_for(data.get("agreement_limits"))
    sound = stats()
    assert not _common.outside(sound, limits), sound
    assert sound["rest_d"] < 1e-4           # no router term on either side

    def biased_route(logits, top_k, norm_topk=True, *, score, select_bias,
                     gate_scale):
        scores = jax.nn.sigmoid(logits.astype(jnp.float32)) + select_bias
        topv, topi = jax.lax.top_k(scores, top_k)
        return scores, gate_scale * topv / topv.sum(-1, keepdims=True), topi

    monkeypatch.setattr(moe, "route", biased_route)
    faulty = stats()
    assert "logit_rel_d" in _common.outside(faulty, limits), faulty
    assert faulty["logit_rel_d"] > 5 * sound["logit_rel_d"]


# -- the cell at test size through JaxTrainer.fit ----------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with ``tiny-kanana2`` added: a config file
    of the ``kanana2`` arch at test size (and the same with the gate
    scale broken on the program's side alone) and the tiny traffic file;
    the arch's own reference, FLOP count, size check and readers are the
    repository's."""
    root = os.path.join(str(tmp_path_factory.mktemp("kanana2")), "root")
    shutil.copytree(os.path.join(_tinycells.REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(_tinycells.TINY, "tiny-kanana2.config.json")) as f:
        sound = json.load(f)
    broken = json.loads(json.dumps(sound))
    broken["name"] = "tiny-kanana2-gate-scale-1"
    broken["factory_kwargs"]["expert_gate_scale"] = 1.0
    for data in (sound, broken):
        with open(os.path.join(root, "chipbench", "configs",
                               data["name"] + ".json"), "w") as f:
            json.dump(data, f)
    shutil.copy(os.path.join(_tinycells.TINY, "tiny-train.traffic.json"),
                os.path.join(root, "chipbench/traffic/tiny-train.json"))
    bench = spec.load_benchmark()
    for data in (sound, broken):
        name = data["name"]
        bench["configs"].append({
            "name": name, "source": "ray_tpu.models",
            "file": f"chipbench/configs/{name}.json", "reduced": [],
            "why": "test-sized rehearsal"})
        bench["workloads"].append({
            "name": name, "config": name, "traffic": "tiny-train",
            "chips": 1, "why": "test-sized rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", []):
                m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _run_cell(root: str, name: str) -> dict:
    code = (
        "import json\n"
        "from chipbench import run\n"
        f"res = run.run_cell({name!r}, seed=3000000023, "
        f"seconds=3.0, trace=False, root={root!r}, rehearsal=dict(num_cpus=4, "
        "num_tpus=2, object_store_memory=128 * 1024 * 1024))\n"
        "print('RESULT ' + json.dumps(res))\n")
    env = dict(os.environ, PYTHONPATH=_tinycells.REPO,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(next(ln for ln in proc.stdout.splitlines()
                           if ln.startswith("RESULT "))[len("RESULT "):])


def test_the_tiny_cell_trains_through_jaxtrainer_and_is_correct(root):
    """``JaxTrainer.fit`` -> ``ray_tpu.data`` -> ``make_train_step`` with
    the default step options, the program's logits against the
    reference's token by token (4 of 8 experts held, the shared expert,
    the dense layer, the biased choice), on fake chips. The result says
    ``platform: cpu``."""
    res = _run_cell(root, "tiny-kanana2")
    assert res["correct"] is True, res["notes"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert {"train_tok_s_chip", "setup_s"} <= set(res["metrics"])
    note = next(n for n in res["notes"] if n.startswith("train:"))
    assert "+ the rest 0.00000" in note          # no router term in the loss
    counters = next(n for n in res["notes"]
                    if n.startswith("the last step's counters:"))
    for name in ("router_bias_absmax", "moe_bias_swapped", "moe_held_share",
                 "moe_load_max"):
        assert name in counters
    assert "moe_expert_counts" not in counters
    assert all(f"check {name}: ok" in res["notes"] for name in (
        "program_agrees_with_reference", "first_step_is_the_compared_forward",
        "step_moves_the_weights", "step_compiled_once"))


def test_the_tiny_cell_with_the_gate_scale_broken_is_not_correct(root):
    """The same cell whose PROGRAM scales its gates by 1.0 where the file
    (and so the reference) states 2.448: the size check names the key, so
    the harness's own entry refuses it; with the check out of the way the
    run ends ``correct: false`` by the comparison with the reference."""
    data = spec.load_json("chipbench", "configs",
                          "tiny-kanana2-gate-scale-1.json", root=root)
    with pytest.raises(spec.SpecError, match="routed_scaling_factor"):
        spec.load_part("sizes", "kanana2").check(data, spec.model_config(data))
    # the reference reads the gate scale from the model's configuration:
    # give it the published one on its side alone
    path = os.path.join(root, "chipbench", "reference", "kanana2.py")
    with open(path) as f:
        text = f.read()
    assert "float(cfg.expert_gate_scale)" in text
    with open(path, "w") as f:
        f.write(text.replace("float(cfg.expert_gate_scale)", "2.448"))
    res = _run_cell(root, "tiny-kanana2-gate-scale-1")
    assert res["correct"] is False
    assert "check program_agrees_with_reference: FAILED" in res["notes"]
    assert any(n.startswith("compared logit_rel_d") and n.endswith("OUTSIDE")
               for n in res["notes"])
