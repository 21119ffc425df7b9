"""``step_kda_kernel_ms`` (``chipbench/layer_metrics``): the gated delta
rule's Pallas kernels in a traced train step, on a hand-written trace
whose answer is computed by hand; None on the parent's program, whose
delta rule is a plain XLA scan, and with no trace at all; and its entry in
``BENCHMARK.json``. CPU only."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import spec, xplane

CELL = "train-kimilinear-ep32share"
FWD = "jit(train_step)/jvp(layers)/closed_call"
BWD = "jit(train_step)/transpose(jvp(layers))/closed_call/checkpoint"
# One device, two runs of the train step in 20 us. Leaves (us):
#   fwd.1     0-2   attn_linear / attn_core: the forward kernel
#   fusion.2  2-3   attn_linear / attn_core: beta's transpose, plain XLA
#   fwd.3     3-5   attn_linear / attn_core: the forward kernel, recomputed
#   bwd.4     5-10  attn_linear / attn_core: the backward kernel
#   fwd.5    10-14  attn_full / attn_core: the latent layer's kernel
#   fusion.6 14-20  attn_linear / kda_conv
# the delta rule 2+1+2+5 = 10 us, of it in kernels 9; two runs. The kernels'
# names end as the step's do: ``jit(_launch)/pallas_call`` (the launch is a
# jitted function of its own; XLA inlines it under the caller's scopes).
KERNELS = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 4000000 }
    events { metadata_id: 6 offset_ps: 14000000 duration_ps: 6000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 10 offset_ps: 10000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fwd.1 = bf16[8]{0} custom-call(bf16[8]{0} %a)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_linear/attn_core/jit(_launch)/pallas_call:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_linear/attn_core/transpose:" } } }
  event_metadata { key: 3 value { id: 3 name: "%fwd.3 = bf16[8]{0} custom-call(bf16[8]{0} %c)"
    stats { metadata_id: 1 str_value: "BWD/rematted_computation/attn/attn_linear/attn_core/jit(_launch)/pallas_call:" } } }
  event_metadata { key: 4 value { id: 4 name: "%bwd.4 = bf16[8]{0} custom-call(bf16[8]{0} %d)"
    stats { metadata_id: 1 str_value: "BWD/attn/attn_linear/attn_core/jit(_launch)/pallas_call:" } } }
  event_metadata { key: 5 value { id: 5 name: "%fwd.5 = bf16[8]{0} custom-call(bf16[8]{0} %e)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_full/attn_core/jvp()/pallas_call:" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = bf16[8]{0} fusion(bf16[8]{0} %f)"
    stats { metadata_id: 1 str_value: "FWD/attn/attn_linear/kda_conv/mul:" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_train_step(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
'''.replace("FWD", FWD).replace("BWD", BWD)
# the parent's program: the same delta rule as a scan's fusions
SCAN = KERNELS.replace("attn_linear/attn_core/jit(_launch)/pallas_call",
                       "attn_linear/attn_core/jvp()/while/body/dot_general")


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


def test_the_kernels_under_attn_linear_on_the_hand_trace(tmp_path):
    run = _run(tmp_path, KERNELS)
    assert _read("step_kda_kernel_ms", run) == pytest.approx(4.5e-3)
    assert any("scope attn_linear, Pallas kernels" in n for n in run["notes"])
    # the whole delta rule holds them and the transpose outside them
    assert _read("step_kda_core_ms", run) == pytest.approx(5.0e-3)
    # the accepted reader counts EVERY kernel under attn: these and the
    # latent layer's (PERF.md section 7)
    assert _read("step_attn_kernel_ms", run) == pytest.approx(6.5e-3)


def test_a_delta_rule_in_plain_xla_reads_nothing(tmp_path):
    run = _run(tmp_path, SCAN)
    assert _read("step_kda_kernel_ms", run) is None     # and does not raise
    assert _read("step_kda_core_ms", run) == pytest.approx(5.0e-3)


@pytest.mark.parametrize("run", [{}, {"trace": None}, {"trace_dir": "/none"}])
def test_no_trace_reads_nothing(run):
    assert _read("step_kda_kernel_ms", run) is None


def test_the_benchmark_names_it_for_the_one_cell_that_has_the_kernels():
    """Found by its name, wherever later PRs' entries put it in the list."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m for m in bench["per_layer"]
            if m["name"] == "step_kda_kernel_ms"] == [{
                "name": "step_kda_kernel_ms", "unit": "ms", "better": "lower",
                "source": "device_trace", "layer": "model step",
                "moves": "train_tok_s_chip", "workloads": [CELL]}]
