"""Flight-recorder tracing plane.

Modeled on the reference's task-event observability surface (SURVEY.md
§5 — TaskEventBuffer batching worker-side events, the GCS's bounded
task-event store, `ray timeline` Chrome-trace export): every hop of a
task's life stamps a phase onto the EXISTING control-plane messages, the
head merges them into one lifecycle record per task, and timeline()
renders per-phase sub-spans with flow arrows — for all four dispatch
paths (head task, leased direct task, head-routed actor call, direct
actor call), with chaos-plane faults visible as instant events in the
same trace.
"""

from __future__ import annotations

import json
import time

import pytest

import ray_tpu
from ray_tpu._private import events as ev_mod
from ray_tpu._private import faultinject
from ray_tpu._private.worker_context import global_runtime
from ray_tpu.util import metrics as um
from ray_tpu.util import state as us
from ray_tpu.util import tracing


@pytest.fixture(scope="module", autouse=True)
def cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def _wait(pred, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(0.05)
    raise TimeoutError(f"never happened: {msg}")


def _lifecycle(pred=lambda e: True):
    """Lifecycle events (carry phases) currently in the head table."""
    return [e for e in us.get_task_events()
            if isinstance(e, dict) and e.get("phases") and pred(e)]


# ------------------------------------------------- four dispatch paths


def test_head_task_lifecycle_phases():
    """Explicit-strategy tasks ride the head: submit→enqueue→dispatch→
    recv→exec — the head-routed half of the phase vocabulary."""
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    rt = global_runtime()

    @ray_tpu.remote
    def head_routed():
        return 1

    ref = head_routed.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(
            node_id=rt.node_id, soft=False)).remote()
    assert ray_tpu.get(ref) == 1
    evs = _wait(lambda: _lifecycle(
        lambda e: e.get("name") == "head_routed"
        and "exec_end" in e["phases"]), msg="head task lifecycle event")
    ph = evs[-1]["phases"]
    for phase in ("submit", "enqueue", "dispatch", "recv",
                  "exec_start", "exec_end", "seal"):
        assert phase in ph, f"missing {phase}: {sorted(ph)}"
    assert "push" not in ph  # head-routed, not direct


def test_leased_direct_task_phases():
    @ray_tpu.remote
    def leased(x):
        return x * 2

    rt = global_runtime()
    assert ray_tpu.get(leased.remote(1)) == 2
    _wait(lambda: len(rt._direct.lease_pools) > 0, msg="lease granted")
    for i in range(5):
        assert ray_tpu.get(leased.remote(i)) == i * 2
    evs = _wait(lambda: _lifecycle(
        lambda e: e.get("name") == "leased" and "push" in e["phases"]
        and "exec_end" in e["phases"]), msg="direct lease lifecycle")
    ph = evs[-1]["phases"]
    for phase in ("submit", "push", "recv", "exec_start", "exec_end",
                  "seal"):
        assert phase in ph, f"missing {phase}: {sorted(ph)}"
    # Acceptance: ≥5 distinct lifecycle phases per direct-mode task.
    assert len(ph) >= 5
    # Same clock (single host): stamps are monotonic along the route.
    order = [ph[p] for p in ("submit", "push", "recv", "exec_start",
                             "exec_end", "seal") if p in ph]
    assert order == sorted(order)


def test_actor_call_phases_head_and_direct():
    @ray_tpu.remote
    class Echo:
        def ping(self, x=None):
            return x

    a = Echo.remote()
    rt = global_runtime()
    # First call rides the head (no grant yet): head-routed actor path.
    assert ray_tpu.get(a.ping.remote(0)) == 0
    _wait(lambda: rt._direct.routes[a._actor_id].mode == "direct",
          msg="actor route direct")
    for i in range(5):
        assert ray_tpu.get(a.ping.remote(i)) == i
    head_call = _wait(lambda: _lifecycle(
        lambda e: e.get("actor_id") == a._actor_id
        and "dispatch" in e["phases"]), msg="head-routed actor lifecycle")
    assert "enqueue" in head_call[-1]["phases"]
    direct_call = _wait(lambda: _lifecycle(
        lambda e: e.get("actor_id") == a._actor_id
        and "push" in e["phases"]), msg="direct actor lifecycle")
    ph = direct_call[-1]["phases"]
    assert len(ph) >= 5
    for phase in ("submit", "push", "recv", "exec_start", "exec_end"):
        assert phase in ph
    ray_tpu.kill(a)


def test_resolve_phase_recorded():
    @ray_tpu.remote
    def produce():
        return 41

    assert ray_tpu.get(produce.remote()) == 41
    evs = _wait(lambda: _lifecycle(
        lambda e: e.get("name") == "produce"
        # The owner's stamp and the worker's arrive on separate casts.
        and {"resolve", "exec_end"} <= set(e["phases"])),
        msg="resolve stamp")
    ph = evs[-1]["phases"]
    assert ph["resolve"] >= ph["exec_end"] - 0.001


def test_resolve_confirmation_beats_registration():
    """Direct tasks register their return ids via the worker's socket
    report while a local-mode owner confirms seals in-process — the
    confirmation can win that race. The stamp must be parked and
    claimed by the late registration, not silently dropped."""
    t = ev_mod.EventTable(100)
    t.resolve(["oid-early"], 123.0)           # owner confirm first
    t.register_oids("task-early", ["oid-early"])  # worker report second
    rec = t.task_record("task-early")
    assert rec is not None and rec["phases"]["resolve"] == 123.0
    # Normal order still works and the parked entry was consumed.
    t.register_oids("task-late", ["oid-late"])
    t.resolve(["oid-late"], 456.0)
    assert t.task_record("task-late")["phases"]["resolve"] == 456.0
    assert not t._pending_resolve


# ------------------------------------------------- clock alignment


def test_clock_offset_alignment_monotonic():
    """Pure-function check: a worker node whose clock runs AHEAD of the
    head makes raw cross-node stamps non-monotonic; align_phases maps
    everything onto the head clock and restores monotonicity."""
    skew = 5.0  # node clock = head clock + 5 s
    t0 = 1000.0
    event = {
        "node_id": "node-b", "owner_node_id": "node-a",
        "phases": {
            "submit": t0,              # owner on node-a (offset 0)
            "push": t0 + 0.001,
            "recv": t0 + 0.002 + skew,  # worker stamps carry the skew
            "exec_start": t0 + 0.003 + skew,
            "exec_end": t0 + 0.010 + skew,
            "seal": t0 + 0.011 + skew,
            "resolve": t0 + 0.013,
        },
    }
    raw = [event["phases"][p] for p in ev_mod.PHASE_ORDER
           if p in event["phases"]]
    assert raw != sorted(raw)  # skew breaks raw ordering (resolve<seal)
    aligned = ev_mod.align_phases(
        event, {"node-b": skew, "node-a": 0.0}, "node-head")
    seq = [aligned[p] for p in ev_mod.PHASE_ORDER if p in aligned]
    assert seq == sorted(seq)
    assert abs(aligned["recv"] - (t0 + 0.002)) < 1e-9


def test_clock_offsets_served_with_events():
    data = us.get_timeline_data()
    assert "clock_offsets" in data and isinstance(data["clock_offsets"],
                                                  dict)
    assert data["head_node_id"]


# ------------------------------------------------- chaos visibility


def test_chaos_events_visible_in_trace():
    @ray_tpu.remote
    class C:
        def ping(self):
            return 1

    a = C.remote()
    rt = global_runtime()
    ray_tpu.get(a.ping.remote())
    _wait(lambda: rt._direct.routes[a._actor_id].mode == "direct",
          msg="route direct")
    with faultinject.inject(
            {"rules": [{"kind": "direct_push", "delay_ms": 1}]}) as plane:
        for _ in range(5):
            ray_tpu.get(a.ping.remote())
        assert plane.stats.get("delay:direct_push", 0) >= 5
        trace = us.timeline()
    chaos = [e for e in trace if e.get("cat") == "chaos"]
    assert len(chaos) >= 5
    assert any(e["name"] == "fault:delay:direct_push" for e in chaos)
    assert all(e["ph"] == "i" for e in chaos)
    ray_tpu.kill(a)


# ------------------------------------------------- timeline rendering


def test_timeline_round_trip_valid_chrome_trace(tmp_path):
    @ray_tpu.remote
    def traced_direct(x):
        time.sleep(0.005)
        return x

    rt = global_runtime()
    ray_tpu.get(traced_direct.remote(0))
    _wait(lambda: len(rt._direct.lease_pools) > 0, msg="lease")
    for i in range(5):
        ray_tpu.get(traced_direct.remote(i))
    _wait(lambda: _lifecycle(
        lambda e: e.get("name") == "traced_direct"
        and "push" in e["phases"] and "resolve" in e["phases"]),
        msg="direct lifecycle with resolve")
    path = us.timeline(str(tmp_path / "trace.json"))
    trace = json.load(open(path))  # valid JSON round trip
    assert isinstance(trace, list) and trace
    for e in trace:
        assert isinstance(e["ts"], (int, float))
        assert e["ph"] in ("X", "i", "s", "t", "f")
        assert "pid" in e and "tid" in e if e["ph"] == "X" else True
    # One task shows ≥5 distinct lifecycle phase sub-spans.
    by_task: dict = {}
    for e in trace:
        if e.get("cat") == "phase" \
                and e["args"].get("task_id") is not None:
            by_task.setdefault(e["args"]["task_id"], set()).add(e["name"])
    assert by_task and max(len(v) for v in by_task.values()) >= 5, by_task
    # Flow arrows connect submit → exec → resolve across tracks.
    flows = [e for e in trace if e.get("cat") == "lifecycle"]
    assert any(e["ph"] == "s" for e in flows)
    assert any(e["ph"] == "f" and e.get("bp") == "e" for e in flows)
    # The classic exec span survives for existing tooling.
    assert any(e.get("cat") == "task" and e["name"] == "traced_direct"
               and e["dur"] > 0 for e in trace)


def test_user_span_carries_worker_id():
    @ray_tpu.remote
    def spanner():
        with tracing.span("inner", rows=3):
            time.sleep(0.001)
        return 1

    assert ray_tpu.get(spanner.remote()) == 1
    evs = _wait(lambda: [
        e for e in us.get_task_events()
        if isinstance(e, dict) and e.get("event") == "span"
        and e.get("name") == "inner"], msg="user span event")
    ev = evs[-1]
    assert ev["worker_id"], f"span lost its worker id: {ev}"
    assert ev["task_id"]


# ------------------------------------------------- metrics surfaces


def test_phase_histograms_in_runtime_stats():
    @ray_tpu.remote
    def histed():
        return 1

    ray_tpu.get([histed.remote() for _ in range(4)])

    def _snap():
        h = global_runtime().conn.call("runtime_stats", {}, timeout=10)
        return h.get("histograms") or None

    hists = _wait(_snap, msg="phase histograms populated")
    assert "exec" in hists and hists["exec"]["count"] > 0
    assert "queue_wait" in hists or "dispatch" in hists
    text = um.runtime_stats_text()
    assert "# TYPE ray_tpu_phase_exec_seconds histogram" in text
    assert "ray_tpu_phase_exec_seconds_count" in text


def test_summarize_tasks_phase_breakdown():
    @ray_tpu.remote
    def summed():
        time.sleep(0.01)
        return 1

    ray_tpu.get([summed.remote() for _ in range(3)])
    time.sleep(0.3)
    summary = us.summarize_tasks()
    assert summary["summed"]["total"] >= 3
    lat = _wait(lambda: us.summarize_tasks()["summed"].get(
        "phase_latency_s"), msg="phase latency breakdown")
    assert lat["exec"]["p50"] >= 0.008
    assert lat["exec"]["p95"] >= lat["exec"]["p50"]
    assert "queue_wait" in lat


def test_prometheus_label_values_escaped():
    c = um.Counter("escape_total", tag_keys=("k",))
    c.inc(1.0, {"k": 'a"b\\c\nd'})
    um.flush_all_of(c)
    def _scrape():
        t = um.prometheus_text()
        return t if "escape_total" in t else None

    text = _wait(_scrape, msg="escaped counter scraped")
    assert 'k="a\\"b\\\\c\\nd"' in text
    # No raw newline may survive inside a label value (it would split
    # the sample line and corrupt the whole exposition).
    for line in text.splitlines():
        if "escape_total" in line and "{" in line:
            assert line.count("{") == line.count("}")


def test_cluster_rpc_counters_aggregated():
    rt = global_runtime()
    rt.report_rpc_now()
    rt.conn.flush_casts()
    def _mine():
        s = um.cluster_rpc_counters()
        return s if rt.client_id in s.get("clients", {}) else None

    snap = _wait(_mine, msg="driver counters reach the head")
    mine = snap["clients"][rt.client_id]
    assert mine["head"]["frames_sent"] > 0
    assert isinstance(mine["head"]["sent_kinds"], dict)
    assert snap["total_head_frames"] >= mine["head"]["frames_sent"]
    # Workers report on the amortized cadence too (release loop fires
    # an immediate first report at boot).
    _wait(lambda: any(cid.startswith("worker-")
                      for cid in um.cluster_rpc_counters()["clients"]),
          msg="worker counters reach the head")


# ------------------------------------------------- table behavior


def test_event_table_bounded_and_merging():
    t = ev_mod.EventTable(maxlen=4)
    t.merge({"task_id": "t1", "name": "a",
             "phases": {"submit": 1.0}})
    t.merge({"task_id": "t1", "name": "a", "worker_id": "w1",
             "phases": {"exec_start": 2.0, "exec_end": 3.0}})
    evs = list(t)
    assert len(evs) == 1  # merged, not duplicated
    assert evs[0]["phases"] == {"submit": 1.0, "exec_start": 2.0,
                                "exec_end": 3.0}
    assert t.phase_hists["exec"].count == 1
    for i in range(10):
        t.append({"event": "chaos", "ts": float(i)})
    assert len(t) == 4  # bounded
    # resolve attribution through the oid index
    t2 = ev_mod.EventTable(maxlen=8)
    t2.register_oids("t9", ["oid1"])
    t2.merge({"task_id": "t9", "name": "b",
              "phases": {"exec_end": 1.0, "seal": 1.5}})
    t2.resolve(["oid1"], 2.5)
    ev = [e for e in t2 if e.get("task_id") == "t9"][0]
    assert ev["phases"]["resolve"] == 2.5
    assert t2.phase_hists["result_transfer"].count == 1
