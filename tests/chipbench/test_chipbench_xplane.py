"""The trace reduction on a small recorded v5e trace kept with the
benchmark, and on a hand-written trace whose answers are computed by
hand. CPU only: reading a trace needs jax, not a chip."""

from __future__ import annotations

import os

import pytest

from chipbench import xplane

FIXTURE = os.path.join(os.path.dirname(xplane.__file__), "fixtures",
                       "v5e_attention_5steps.xplane.pb")

# Two devices. Times in ps from each line's timestamp_ns (1000 ns).
#   device 0 ops:  fusion 0-2 us, all-gather 3-4 us (alone: exposed 1 us),
#                  pallas kernel 4-7 us, all-reduce 9-10 us overlapping the
#                  async all-gather-start span 6.5-9.5 us
#   device 1 ops:  fusion 0-4 us only
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 6500000 duration_ps: 3000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 6 offset_ps: 0 duration_ps: 7000000 }
    events { metadata_id: 6 offset_ps: 9000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.1 = f32[8]{0} all-gather(f32[2]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "%k.1 = (bf16[4,512,64]{2,1,0}, f32[4,512,1]{2,1,0}) custom-call(bf16[4,512,64]{2,1,0} %q, bf16[4,512,64]{2,1,0} %k, bf16[4,512,64]{2,1,0} %v), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %g)" } }
  event_metadata { key: 5 value { id: 5 name: "%all-gather-start.3 = (f32[2]{0}, f32[8]{0}) all-gather-start(f32[2]{0} %w)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_train_step(123)" } }
}
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "engine.sample" } }
}
'''


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData

    return xplane.from_profile_data(ProfileData.from_text_proto(HAND))


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(FIXTURE)


def test_hand_trace_busy_and_idle(hand):
    # device 0: 2 + 1 + 3 + 1 = 7 us busy; device 1: 4 us; mean 5.5 us
    assert xplane.busy_s(hand) == pytest.approx(5.5e-6)


def test_hand_trace_kernel_time(hand):
    # one 3-us kernel on device 0, none on device 1: mean 1.5 us
    assert [e.short for e in xplane.kernel_events(hand)] == ["k.1"]
    assert xplane.kernel_s(hand) == pytest.approx(1.5e-6)


def test_hand_trace_collective_exposure(hand):
    # device 0: all-gather 3-4 (1 us alone); async all-gather-start
    # 6.5-9.5 overlaps the kernel until 7, so 7-9 (2 us) is exposed, plus
    # 9-9.5 which the all-reduce (itself a collective) covers; all-reduce
    # 9-10 alone: union of collectives [3,4] + [6.5,10] = 4.5 us, minus
    # compute overlap [6.5,7] = 0.5 us -> 4 us. device 1: none. mean 2 us.
    assert xplane.collective_exposed_s(hand) == pytest.approx(2e-6)


def test_hand_trace_gaps_are_named_by_what_they_waited_for(hand):
    # device 0 busy: [0,2] [3,7] [9,10] -> gaps 2-3 (1 us) and 7-9 (2 us).
    # jit_train_step runs 0-7 and 9-10: the first gap is inside a program
    # (no collective in flight: a stall), the second is between programs,
    # where the host's engine.sample span covers 7-9.
    gaps = dict(xplane.idle_gaps(hand, min_gap_ns=500.0))
    assert gaps == {"engine.sample": pytest.approx(2e-6),
                    "in jit_train_step: stall": pytest.approx(1e-6)}


def test_hand_trace_labels_and_modules(hand):
    top = xplane.top_device_ops(hand, 2)
    # fusion.1: (2 us on device 0 + 4 us on device 1) / 2 devices
    assert top[0] == ["fusion.1 fusion f32[8]", pytest.approx(3e-6)]
    assert top[1][0].startswith("k.1 pallas (bf16[4,512,64], f32[4,512,1])")
    assert [m.dur for m in hand.devices[0].modules] == [7000.0, 1000.0]


# One device, a train step whose layer scan is a ``while`` (times in us):
#   while.1 0-100 encloses: all-gather.1 10-40 (alone), fusion.1 40-50,
#     conditional.1 50-70 enclosing fusion.2 52-68, fusion.3 70-90
#   async all-gather-start.2 88-98 (2 us beside fusion.3, 8 us alone)
#   then, in a second program, while.9 100-110 whose body was not recorded
SCAN = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 50000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 52000000 duration_ps: 16000000 }
    events { metadata_id: 6 offset_ps: 70000000 duration_ps: 20000000 }
    events { metadata_id: 7 offset_ps: 100000000 duration_ps: 10000000 }
  }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 8 offset_ps: 88000000 duration_ps: 10000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 10 offset_ps: 100000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%c, body=%b" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.1 = f32[8]{0} all-gather(f32[2]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 4 value { id: 4 name: "%conditional.1 = f32[8]{0} conditional(pred[] %q, f32[8]{0} %a, f32[8]{0} %b)" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 7 value { id: 7 name: "%while.9 = (s32[]) while((s32[]) %t), condition=%c, body=%b" } }
  event_metadata { key: 8 value { id: 8 name: "%all-gather-start.2 = (f32[2]{0}, f32[8]{0}) all-gather-start(f32[2]{0} %w)" } }
  event_metadata { key: 9 value { id: 9 name: "jit_train_step(123)" } }
  event_metadata { key: 10 value { id: 10 name: "jit_other(456)" } }
}
'''


@pytest.fixture(scope="module")
def scan():
    from jax.profiler import ProfileData

    return xplane.from_profile_data(ProfileData.from_text_proto(SCAN))


def test_control_flow_containers_are_not_instructions(scan):
    d = scan.devices[0]
    assert sorted(e.short for e in d.containers) == ["conditional.1",
                                                     "while.1"]
    # a loop whose body the profiler did not record stays a leaf
    assert "while.9" in [e.short for e in d.ops] and len(d.ops) == 5
    assert dict(xplane.container_ops(scan)) == {
        "while.1 while (s32[], f32[8])": pytest.approx(100e-6),
        "while.1/conditional.1 conditional f32[8]": pytest.approx(20e-6)}
    # the instructions that ran, each named with the scan it is in
    assert dict(xplane.top_device_ops(scan)) == {
        "while.1/all-gather.1 all-gather f32[8]": pytest.approx(30e-6),
        "while.1/fusion.3 fusion f32[8]": pytest.approx(20e-6),
        "conditional.1/fusion.2 fusion f32[8]": pytest.approx(16e-6),
        "while.1/fusion.1 fusion f32[8]": pytest.approx(10e-6),
        "while.9 while (s32[])": pytest.approx(10e-6)}


def test_a_stall_inside_the_layer_scan_is_idle(scan):
    # leaves: 30 + 10 + 16 + 20 + 10 = 86 us of the 110; the gaps 0-10,
    # 50-52, 68-70 and 90-100 inside while.1 are idle, not busy. (A gap
    # is what lies between two busy intervals: 0-10 is not listed.)
    assert xplane.busy_s(scan) == pytest.approx(86e-6)
    gaps = dict(xplane.idle_gaps(scan, min_gap_ns=500.0))
    assert gaps == {
        "in jit_train_step: stall": pytest.approx(4e-6),
        "in jit_train_step: all-gather-start.2 all-gather-start "
        "(f32[2], f32[8])": pytest.approx(10e-6)}


def test_a_collective_alone_inside_the_layer_scan_is_exposed(scan):
    # all-gather.1 runs 10-40 with nothing beside it (while.1 is no
    # instruction): 30 us; the async all-gather 88-98 has fusion.3 beside
    # it until 90: 8 us more.
    assert xplane.collective_exposed_s(scan) == pytest.approx(38e-6)
    assert xplane.exposed_collectives(scan) == [
        ["while.1/all-gather.1 all-gather f32[8]", pytest.approx(30e-6)],
        ["all-gather-start.2 all-gather-start (f32[2], f32[8])",
         pytest.approx(8e-6)]]


def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [
        (0, 3), (5, 7)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 1), (4, 6)], []) == [(0, 1), (4, 6)]
    assert xplane.total([(0, 3), (5, 7)]) == 5


def test_recorded_trace_shape(recorded):
    """What the v5e wrote for 5 steps of attention forward + backward at
    [2, 4096, 32, 128] bf16 plus one matmul (see PERF.md section 6)."""
    assert len(recorded.devices) == 1
    d = recorded.devices[0]
    assert len(d.ops) == 235 and len(d.modules) == 5
    assert "python" in recorded.host
    assert all(m.name.startswith("jit_fwd_bwd(") for m in d.modules)


def test_recorded_trace_busy_against_a_brute_force_timeline(recorded):
    d = recorded.devices[0]
    t0 = min(e.start for e in d.ops)
    # 1-us raster of the op intervals, computed the slow way.
    n = int((max(e.end for e in d.ops) - t0) / 1000.0) + 2
    covered = bytearray(n)
    for e in d.ops:
        for i in range(int((e.start - t0) / 1000.0),
                       int((e.end - t0) / 1000.0)):
            covered[i] = 1
    assert xplane.busy_s(recorded) == pytest.approx(sum(covered) * 1e-6,
                                                    rel=0.01)
    # Known by hand from the file: 0.177 s busy (of a 0.225 s span).
    assert xplane.busy_s(recorded) == pytest.approx(0.17705, rel=1e-3)


def test_recorded_trace_kernels(recorded):
    ks = xplane.kernel_events(recorded)
    assert len(ks) == 15                      # 3 kernels x 5 steps
    assert xplane.kernel_s(recorded) == pytest.approx(
        sum(e.dur for e in ks) / 1e9)
    # Known by hand from the file: 0.150 of the 0.177 s busy are kernels.
    assert xplane.kernel_s(recorded) == pytest.approx(0.15030, rel=1e-3)
    assert all(e.label.split()[1] == "pallas" for e in ks)
    assert xplane.collective_exposed_s(recorded) == 0.0
    assert recorded.devices[0].containers == []
