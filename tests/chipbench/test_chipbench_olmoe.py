"""What the ``olmoe`` configuration brings to the benchmark: its three
per-layer readers on a hand-written trace whose answers are computed by
hand, its FLOP count against the program's own parameter count, and its
size check. CPU only; the cell itself is rehearsed by
``test_chipbench_rehearsal.py`` and held to the contract by
``test_chipbench_spec.py``, which pick it up by name."""

from __future__ import annotations

import pytest

from chipbench import spec, xplane

MOE_READERS = ("step_moe_experts_ms", "step_moe_route_ms",
               "moe_experts_peak_share")
FWD = "jit(train_step)/jvp(layers)/while/body/closed_call/moe"
BWD = "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint"
# One device, two runs of the train step in 20 us. Leaves (us):
#   gmm.1     0-4    moe_experts forward     gmm.2    4-7   moe_experts recompute
#   tgmm.3    7-12   moe_experts backward    fusion.4 12-14 moe_router forward
#   gather.5  14-15  moe_dispatch recompute  fusion.6 15-18 moe_combine backward
#   fusion.7  18-19  mlp forward (a dense block: no sub-scope)
#   copy.8    19-20  no tf_op
# experts 12 us, routing 6 us, over two runs: 6e-3 and 3e-3 ms a step.
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 12000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 14000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 15000000 duration_ps: 3000000 }
    events { metadata_id: 7 offset_ps: 18000000 duration_ps: 1000000 }
    events { metadata_id: 8 offset_ps: 19000000 duration_ps: 1000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 9 offset_ps: 10000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%gmm.1 = bf16[8]{0} custom-call(bf16[8]{0} %a)"
    stats { metadata_id: 1 str_value: "FWD/moe_experts/jit(gmm)/pallas_call:" } } }
  event_metadata { key: 2 value { id: 2 name: "%gmm.2 = bf16[8]{0} custom-call(bf16[8]{0} %b)"
    stats { metadata_id: 1 str_value: "BWD/rematted_computation/moe/moe_experts/jit(gmm)/pallas_call:" } } }
  event_metadata { key: 3 value { id: 3 name: "%tgmm.3 = bf16[8]{0} custom-call(bf16[8]{0} %c)"
    stats { metadata_id: 1 str_value: "BWD/moe/moe_experts/jit(tgmm)/pallas_call:" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %d)"
    stats { metadata_id: 1 str_value: "FWD/moe_router/reduce_max:" } } }
  event_metadata { key: 5 value { id: 5 name: "%gather.5 = bf16[8]{0} gather(bf16[8]{0} %e)"
    stats { metadata_id: 1 str_value: "BWD/rematted_computation/moe/moe_dispatch/gather:" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = f32[8]{0} fusion(f32[8]{0} %f)"
    stats { metadata_id: 1 str_value: "jit(train_step)/transpose(jvp())/reshape;BWD/moe/moe_combine/mul:" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %g)"
    stats { metadata_id: 1 str_value: "jit(train_step)/jvp(layers)/while/body/closed_call/mlp/dot_general:" } } }
  event_metadata { key: 8 value { id: 8 name: "%copy.8 = f32[8]{0} copy(f32[8]{0} %h)" } }
  event_metadata { key: 9 value { id: 9 name: "jit_train_step(123)" } }
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
    data = spec.load_json("chipbench", "configs", "olmoe-1b-7b-1chip.json")
    return {"trace": xplane.load(xplane.find_xplane(trace_dir)),
            "trace_dir": trace_dir, "notes": [],
            "cell": {"chips": 1, "config_data": data},
            "train": {"tokens_per_step": 8192},
            "peaks": spec.load_peaks("TPU v5 lite")}


def _read(name: str, run: dict):
    return spec.load_part("layer_metrics", name).read(run)


def test_moe_readers_on_the_hand_trace(tmp_path):
    run = _run(_write(tmp_path, HAND))
    assert _read("step_moe_experts_ms", run) == pytest.approx(6.0e-3)
    assert _read("step_moe_route_ms", run) == pytest.approx(3.0e-3)
    # the benchmark's own reader gives all of it to ``moe``, plus the mlp
    assert _read("step_mlp_ms", run) == pytest.approx(9.5e-3)
    # 9 grouped matmuls' worth of model FLOPs a step (3 forward + 6
    # backward; the recomputed 3 are time only) over 6 us and the peak
    flops = 3 * 2 * 8 * 3 * 2048 * 1024 * 8192
    assert _read("moe_experts_peak_share", run) == pytest.approx(
        100 * flops / 6.0e-6 / 197e12)
    assert any(n.startswith("moe scopes:") and "moe_experts 0.0000" in n
               for n in run["notes"])
    run["peaks"] = None                      # a CPU rehearsal: no share
    assert _read("moe_experts_peak_share", run) is None


@pytest.mark.parametrize("name", MOE_READERS)
def test_moe_readers_return_none_with_nothing_to_read(name, tmp_path):
    assert _read(name, {"trace": None, "trace_dir": None, "notes": []}) is None
    # a program whose mixture of experts has no sub-scopes (or none at
    # all): the instructions are there, under ``moe`` or ``mlp`` alone
    plain = HAND
    for sub in ("moe_experts", "moe_router", "moe_dispatch", "moe_combine"):
        plain = plain.replace(f"/{sub}/", "/")
    run = _run(_write(tmp_path, plain))
    assert _read(name, run) is None
    assert _read("step_mlp_ms", run) == pytest.approx(9.5e-3)
    assert any("none of them on any instruction" in n for n in run["notes"])


def test_olmoe_flop_functions_count_the_programs_parameters():
    data = spec.load_json("chipbench", "configs", "olmoe-1b-7b-1chip.json")
    flops = spec.load_part("flops", data["arch"])
    cfg = spec.model_config(data)
    assert flops.n_params(cfg) == cfg.num_params() == 625_616_896
    # a token passes through 8 of 64 experts, the router, attention, head
    layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert flops.matmul_params(cfg) == layer + 2048 * 50304
    assert flops.expert_matmul_params(cfg) == 8 * 3 * 2048 * 1024
    attn = 2 * 2 * 16 * 128 * (4096 / 2)
    assert flops.train_flops_per_token(cfg, 4096) == 3 * (
        2 * flops.matmul_params(cfg) + attn)
    assert flops.experts_train_flops_per_token(cfg) == 6 * 8 * 3 * 2048 * 1024
    # every layer of the published depth has its own experts
    full = spec.model_config(data, n_layers=16)
    assert flops.n_params(full) == full.num_params()
    assert 6.9e9 < full.num_params() < 7.0e9
    assert 1.1e9 < flops.matmul_params(full) < 1.3e9     # "1B" active


@pytest.mark.parametrize("key,value,kwargs", [
    ("num_experts_per_tok", 8, {"expert_top_k": 2}),
    ("norm_topk_prob", False, {"expert_norm_topk": True}),
    ("router_z_loss_coef", 0.001, {"router_z_weight": 0.0}),
    ("router_aux_loss_coef", 0.01, {"router_aux_weight": 0.02}),
    ("qk_norm", True, {"qk_norm": False}),
    ("dropless", True, {"expert_capacity_factor": 1.25}),
    ("intermediate_size", 1024, {"d_ff": 2048}),
])
def test_olmoe_size_check_names_what_the_factory_runs_differently(
        key, value, kwargs):
    data = spec.load_json("chipbench", "configs", "olmoe-1b-7b-1chip.json")
    check = spec.load_part("sizes", data["arch"]).check
    check(data, spec.model_config(data))
    with pytest.raises(spec.SpecError, match=rf"{key}: the file states "
                                             rf"{value!r}, the factory runs"):
        check(data, spec.model_config(data, **kwargs))
