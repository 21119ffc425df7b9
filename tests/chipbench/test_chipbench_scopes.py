"""``chipbench/scopes.py``: the event metadata of a recorded v5e trace
read with the standard library alone, and the by-part table and the six
``step_*_ms`` readers on a hand-written trace whose answers are computed
by hand. CPU only."""

from __future__ import annotations

import os

import pytest

from chipbench import scopes, spec, xplane

FIXTURE = os.path.join(os.path.dirname(xplane.__file__), "fixtures",
                       "v5e_attention_5steps.xplane.pb")
STEP_READERS = ("step_attn_ms", "step_mlp_ms", "step_head_loss_ms",
                "step_optimizer_ms", "step_recompute_ms", "step_unscoped_ms")

LAYERS = "jit(train_step)/transpose(jvp(layers))/while/body"
# One device, two runs of the train step in 10 us. Leaves (us):
#   fusion.1  0-2   attn forward            fusion.2  2-5   mlp recompute
#   fusion.3  5-6   head_loss backward      fusion.4  6-8   optimizer
#   copy.5    8-9   no tf_op: unscoped      fusion.6  9-10  layers backward
#                                           (its tf_op is a ref_value)
# while.7 spans 0-5 around fusion.1 and fusion.2: a container, in no sum,
# though its own tf_op says attn.
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 9000000 duration_ps: 1000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 8 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 8 offset_ps: 5000000 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"
    stats { metadata_id: 2 str_value: "loop fusion" }
    stats { metadata_id: 1 str_value: "jit(train_step)/jvp(layers)/while/body/closed_call/attn/dot_general:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q)"
    stats { metadata_id: 1 str_value: "LAYERS/closed_call/checkpoint/rematted_computation/mlp/mul:" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %r)"
    stats { metadata_id: 1 str_value: "jit(train_step)/transpose(jvp(head_loss))/mul:" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %s)"
    stats { metadata_id: 1 str_value: "jit(train_step)/optimizer/add:" } } }
  event_metadata { key: 5 value { id: 5 name: "%copy.5 = f32[8]{0} copy(f32[8]{0} %t)" } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = f32[8]{0} fusion(f32[8]{0} %u)"
    stats { metadata_id: 1 ref_value: 3 } } }
  event_metadata { key: 7 value { id: 7 name: "%while.7 = (f32[8]{0}) while((f32[8]{0}) %v)"
    stats { metadata_id: 1 str_value: "jit(train_step)/jvp(layers)/while/body/closed_call/attn/while:" } } }
  event_metadata { key: 8 value { id: 8 name: "jit_train_step(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "hlo_category" } }
  stat_metadata { key: 3 value { id: 3 name: "LAYERS/dynamic_update_slice:" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "data.next_batch" } }
}
'''.replace("LAYERS", LAYERS)


def _write(tmp_path, text: str) -> str:
    """The text proto as a ``.xplane.pb`` where ``start_trace`` puts one."""
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def _run(trace_dir: str) -> dict:
    return {"trace": xplane.load(xplane.find_xplane(trace_dir)),
            "trace_dir": trace_dir, "notes": []}


def _read(name: str, run: dict):
    return spec.load_part("layer_metrics", name).read(run)


def test_recorded_trace_has_tf_op_for_fusion_4():
    names = scopes.op_names(FIXTURE)
    assert list(names) == ["/device:TPU:0"]
    by_short = {text.split(" = ")[0].lstrip("%"): op
                for text, op in names["/device:TPU:0"].items()}
    assert by_short["fusion.4"] == \
        "jit(fwd_bwd)/transpose(jvp())/dot_general:"
    # every leaf instruction of the recorded trace finds its metadata
    trace = xplane.load(FIXTURE)
    leaves = {e.name for e in trace.devices[0].ops}
    assert leaves and len(leaves & set(names["/device:TPU:0"])) >= 10
    tab = scopes.table(trace, names)
    assert sum(tab.values()) == pytest.approx(xplane.busy_s(trace))
    assert {part for part, _ in tab} == {scopes.UNSCOPED}
    assert ("unscoped", "backward") in tab      # transpose(jvp()) is there


def test_hand_trace_table_by_part_and_pass(tmp_path):
    run = _run(_write(tmp_path, HAND))
    found = scopes.of_run(run)
    assert found["runs"] == 2
    assert {k: round(v * 1e6, 6) for k, v in found["table"].items()} == {
        ("attn", "forward"): 2.0, ("mlp", "recompute"): 3.0,
        ("head_loss", "backward"): 1.0, ("optimizer", "forward"): 2.0,
        ("unscoped", "forward"): 1.0, ("layers", "backward"): 1.0}
    # the rows add up to busy: the while is in no row
    assert sum(found["table"].values()) == pytest.approx(
        xplane.busy_s(run["trace"]))
    assert scopes.of_run(run) is found            # made once
    table_lines = [n for n in run["notes"] if n.startswith("scopes:")]
    assert len(table_lines) == 2 and "mlp 0.0000 / 0.0000 / 0.0000" \
        in table_lines[0] and "busy" in table_lines[0]


def test_step_readers_on_the_hand_trace(tmp_path):
    run = _run(_write(tmp_path, HAND))
    got = {name: _read(name, run) for name in STEP_READERS}
    # microseconds over two runs of the step, in ms
    assert got == pytest.approx({
        "step_attn_ms": 1.0e-3, "step_mlp_ms": 1.5e-3,
        "step_head_loss_ms": 0.5e-3, "step_optimizer_ms": 1.0e-3,
        "step_recompute_ms": 1.5e-3, "step_unscoped_ms": 0.5e-3})


def test_two_chips_are_averaged(tmp_path):
    second = HAND.replace("/device:TPU:0", "/device:TPU:1", 1)
    second = second[:second.index('planes { id: 3 name: "/host:CPU"')]
    run = _run(_write(tmp_path, HAND + second.replace("id: 1 name", "id: 2 name", 1)))
    assert len(run["trace"].devices) == 2
    assert _read("step_attn_ms", run) == pytest.approx(1.0e-3)


@pytest.mark.parametrize("name", STEP_READERS)
def test_step_readers_return_none_with_nothing_to_read(name, tmp_path):
    assert _read(name, {"trace": None, "trace_dir": None, "notes": []}) is None
    # the recorded trace: no run of the train step in it
    d = tmp_path / "recorded" / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with open(FIXTURE, "rb") as f:
        (d / "host.xplane.pb").write_bytes(f.read())
    assert _read(name, _run(str(tmp_path / "recorded"))) is None
    # a train step none of whose instructions has a scope: a program
    # from before the scopes
    stale = HAND
    for part in scopes.PARTS:
        stale = stale.replace(f"/{part}/", "/x/").replace(f"({part})", "()")
    run = _run(_write(tmp_path, stale))
    assert _read(name, run) is None
    assert any("no instruction carries a scope" in n for n in run["notes"])

