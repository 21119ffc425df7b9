"""Continuous-profiling plane.

Modeled on the reference's dashboard profiling (py-spy-driven
profile_manager) made ALWAYS-ON: unit tests for the duty-cycled
sampler (bounded tables, kill switch, borrow unification with the
on-demand probe, GIL-starvation exemplars, crash-sidecar join), the
folded-profile algebra the head/CLI share; plus
end-to-end tests asserting a live cluster yields a merged flamegraph
spanning the head and multiple workers purely from piggybacked report
casts, that `ray-tpu profile` renders/exports/diffs it, and that the
`ray_tpu_profile_*` series reach the Prometheus exposition.
"""

from __future__ import annotations

import json
import os
import threading
import time
import traceback

import pytest

import ray_tpu
from ray_tpu._private import config as config_mod
from ray_tpu._private import forensics, profplane
from ray_tpu._private.worker_context import global_runtime
from ray_tpu.util import metrics as um
from ray_tpu.util import state as us


def _wait(pred, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(0.05)
    raise TimeoutError(f"never happened: {msg}")


def _burn_until(stop: threading.Event) -> int:
    # A recognizable busy leaf for the sampler to catch.
    acc = 0
    while not stop.is_set():
        acc += sum(i * i for i in range(500))
    return acc


@pytest.fixture
def busy_thread():
    stop = threading.Event()
    t = threading.Thread(target=_burn_until, args=(stop,), daemon=True)
    t.start()
    yield
    stop.set()
    t.join(timeout=5)


# ========================================== folded-profile algebra


def _frame(name, line="x = compute()", filename="/a/b/mod.py"):
    return traceback.FrameSummary(filename, 10, name, line=line)


def test_fold_stack_and_idle_filter():
    stack = [_frame("outer"), _frame("inner")]
    assert profplane.fold_stack(stack) == "mod.py:outer;mod.py:inner"
    # Wait primitives filter by leaf NAME...
    assert profplane.is_idle_leaf(_frame("wait"))
    assert profplane.is_idle_leaf(_frame("_wait_for_tstate_lock"))
    # ...and C-builtin blocking calls (no Python frame below) by the
    # source line of the caller leaf.
    assert profplane.is_idle_leaf(_frame("loop", line="time.sleep(0.1)"))
    assert profplane.is_idle_leaf(_frame("rx", line="sock.recv_into(buf)"))
    assert not profplane.is_idle_leaf(_frame("loop", line="acc += 1"))


def test_merge_folded_bounded_overflow():
    into: dict = {}
    profplane.merge_folded(into, {f"s{i}": 1 for i in range(8)}, cap=4)
    assert len(into) <= 5  # 4 kept + overflow bucket
    assert into[profplane.OTHER_BUCKET] == 4
    # Existing keys keep accumulating even past the cap.
    profplane.merge_folded(into, {"s0": 3}, cap=4)
    assert into["s0"] == 4


def test_diff_folded_normalized_share():
    # Window A: 10 samples all in f; window B: 20 samples, half in g —
    # per-sample share keeps different-length windows comparable.
    d = profplane.diff_folded({"f": 10}, {"f": 10, "g": 10})
    assert d["f"] == pytest.approx(-0.5)
    assert d["g"] == pytest.approx(0.5)
    assert profplane.diff_folded({"f": 5}, {"f": 10}) == {}


def test_self_time_attributes_leaf_frames():
    st = profplane.self_time({
        "m:a;m:leaf": 3, "m:b;m:leaf": 2, "m:other": 1,
        profplane.OTHER_BUCKET: 99})
    assert st == {"m:leaf": 5, "m:other": 1}


# ========================================== sampler (process-local)


def test_kill_switch_arms_nothing(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PROFILING_ENABLED", "0")
    had = profplane.sampler()
    try:
        profplane.disarm()
        assert not profplane.enabled()
        assert profplane.arm("worker", "w-x") is None
        assert profplane.sampler() is None
        # No sampler -> the report cast ships without a profile field
        # and the task-finish join is a no-op.
        assert profplane.report_summary(force=True) is None
        assert not profplane.note_task_cpu("t", "n", 10.0, 0.0)
    finally:
        monkeypatch.setenv("RAY_TPU_PROFILING_ENABLED", "1")
        if had is not None:
            profplane.arm(had.role, had.ident)


def test_sampler_catches_busy_thread_and_filters_idle(busy_thread):
    s = profplane.ContinuousSampler("test", "t-1", hz=100, duty_cycle=1.0)
    try:
        _wait(lambda: s.samples >= 20, msg="sampler never sampled")
        summary = s.window_summary()
        assert summary["samples"] >= 20
        assert summary["role"] == "test" and summary["pid"] == os.getpid()
        joined = " ".join(summary["folded"])
        assert "_burn_until" in joined
        # This test's own main thread is parked in _wait (leaf:
        # time.sleep) — the idle filter must have kept it out.
        assert not any(k.endswith(":_wait") for k in summary["folded"])
    finally:
        s.stop()


def test_sampler_table_bounded_with_overflow_bucket(busy_thread):
    s = profplane.ContinuousSampler("test", "t-2", hz=100, duty_cycle=1.0,
                                    table_max=16)
    # Drive the sampler synchronously (no racing daemon thread): fill
    # the table to its bound, then sample the live busy thread.
    s.stop()
    s._thread.join(timeout=5)
    with s._swap_lock:
        s._folded.clear()
        s._folded.update({f"preload:s{i}": 1 for i in range(16)})
    for _ in range(5):
        s._sample_once()
    summary = s.window_summary()
    # The busy thread's novel stack could not claim a slot: it landed
    # in the overflow bucket and the dropped counter, every pass.
    assert summary["dropped"] >= 5
    assert summary["folded"].get(profplane.OTHER_BUCKET, 0) >= 5
    assert sum(1 for k in summary["folded"]
               if k != profplane.OTHER_BUCKET) <= 16
    # Window swap resets the table + counters.
    assert s.dropped == 0 and s._win_samples == 0


def test_duty_cycle_bounds_sampling_cost(busy_thread):
    # Default-shape sampler (19 Hz, 20% duty): the measured in-sampler
    # cost over a >1-cycle window must be well under the 3% overhead
    # budget the plane ships with.
    s = profplane.ContinuousSampler("test", "t-3")
    try:
        t0 = time.time()
        _wait(lambda: time.time() - t0 >= 2.2, timeout=10, msg="window")
        summary = s.window_summary()
        wall = summary["end"] - summary["start"]
        assert summary["samples"] > 0
        assert summary["sample_cost_s"] / wall < 0.03
        # Duty cycling really ran: far fewer passes than hz * wall.
        assert summary["samples"] < s.hz * wall * 0.6
    finally:
        s.stop()


def test_gil_exemplar_first_trigger_wins(busy_thread):
    s = profplane.ContinuousSampler("test", "t-4", hz=100, duty_cycle=1.0)
    try:
        _wait(lambda: s.samples >= 10, msg="no samples")
        # wall >> cpu: starved task pins the current window's profile.
        assert s.note_task_cpu("tid-1", "starved", 1.0, 0.01)
        # CPU-bound and short tasks never trigger; first trigger wins.
        assert not s.note_task_cpu("tid-2", "busy", 1.0, 0.9)
        assert not s.note_task_cpu("tid-3", "quick", 0.1, 0.0)
        assert not s.note_task_cpu("tid-4", "starved2", 2.0, 0.0)
        summary = s.window_summary()
        ex = summary["gil_exemplar"]
        assert ex["task_id"] == "tid-1" and ex["name"] == "starved"
        assert ex["folded"]  # snapshot of what the process was doing
        # Consumed: the next window ships clean.
        assert "gil_exemplar" not in s.window_summary()
    finally:
        s.stop()


def test_borrow_unifies_on_demand_probe_no_second_thread(busy_thread):
    # Mostly-idle sampler: the borrow must boost it to continuous.
    s = profplane.ContinuousSampler("test", "t-5", hz=2, duty_cycle=0.05)
    try:
        n_threads = sum(1 for t in threading.enumerate()
                        if t.name == "profplane-sampler")
        res = s.borrow(0.5, hz=100)
        # One sampler thread total — the probe teed off the stream.
        assert sum(1 for t in threading.enumerate()
                   if t.name == "profplane-sampler") == n_threads
        # Boosted past the un-boosted budget (2 Hz * 5% duty * 0.5 s
        # rounds to ~0 passes). Loose bound: on a loaded 1-core box the
        # sampler thread competes for scheduling slots.
        assert res["samples"] >= 3
        assert any("_burn_until" in k for k in res["folded"])
        # The same samples landed in the continuous window table too
        # (one stream, counted once each — not double-sampled).
        assert s._win_samples >= res["samples"]
        assert s.borrows_served == 1 and not s._borrows
    finally:
        s.stop()


def test_profile_worker_rides_armed_sampler(busy_thread):
    # The worker-side on-demand probe path: an armed process serves
    # profile_worker via borrow() — exporter-shape folded output.
    prev = profplane.sampler()
    profplane.disarm()
    try:
        s = profplane.arm("worker", "w-unify")
        assert s is not None
        assert profplane.arm("driver", "ignored") is s  # first role wins
        res = s.borrow(0.4, hz=100)
        assert set(res) == {"samples", "folded", "duration_s", "hz"}
        assert all(isinstance(v, int) for v in res["folded"].values())
    finally:
        profplane.disarm()
        if prev is not None:
            profplane.arm(prev.role, prev.ident)


def test_sidecar_written_and_crash_report_join(tmp_path, busy_thread):
    crash_dir = str(tmp_path)
    sidecar = forensics.profile_path(crash_dir, "w-dead")
    s = profplane.ContinuousSampler("worker", "w-dead", hz=100,
                                    duty_cycle=1.0, sidecar_path=sidecar)
    try:
        _wait(lambda: s.samples >= 10, msg="no samples")
        s.window_summary()
        rec = forensics.read_profile_sidecar(sidecar)
        assert rec is not None and rec["samples"] >= 10
        assert any("_burn_until" in k for k in rec["folded"])
        # The forensics report for a SIGKILL'd worker joins the sidecar:
        # the last window survives a death no handler could observe.
        report = forensics.collect_report(
            "w-dead", "node-1", 1234, term_signal=9, crash_dir=crash_dir)
        assert report["profile"]["samples"] == rec["samples"]
    finally:
        s.stop()


# ========================================== end-to-end (live cluster)


@pytest.fixture(scope="module")
def cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    profplane.disarm()
    cfg = config_mod.GLOBAL_CONFIG
    saved_env = {k: os.environ.get(k) for k in (
        "RAY_TPU_PROFILE_DUTY_CYCLE", "RAY_TPU_PROFILE_HZ",
        "RAY_TPU_PROFILING_WINDOW_S", "RAY_TPU_RPC_REPORT_INTERVAL_S")}
    saved_cfg = (cfg.profiling_window_s, cfg.rpc_report_interval_s)
    # Aggressive cadence so windows ship within test timeouts; workers
    # inherit the env, the driver/head read the patched GLOBAL_CONFIG.
    os.environ.update({
        "RAY_TPU_PROFILE_DUTY_CYCLE": "1.0",
        "RAY_TPU_PROFILE_HZ": "50",
        "RAY_TPU_PROFILING_WINDOW_S": "1.0",
        "RAY_TPU_RPC_REPORT_INTERVAL_S": "0.5",
    })
    cfg.profiling_window_s = 1.0
    cfg.rpc_report_interval_s = 0.5
    ray_tpu.init(num_cpus=2, object_store_memory=64 * 1024 * 1024)
    yield
    ray_tpu.shutdown()
    profplane.disarm()
    cfg.profiling_window_s, cfg.rpc_report_interval_s = saved_cfg
    for k, v in saved_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


@ray_tpu.remote
def _burn(n):
    acc = 0
    for i in range(n):
        acc += i * i
    return acc


def _cluster_pids(prof):
    return {p for w in prof.get("windows", ())
            for p in (w.get("pids") or ())}


def _keep_burning(duration_s=0.0):
    refs = [_burn.remote(150_000) for _ in range(8)]
    ray_tpu.get(refs)


def test_e2e_cluster_profile_spans_head_and_workers(cluster):
    """Acceptance: the merged cluster profile spans the head process
    and >= 2 workers (>= 3 pids total) purely from piggybacked report
    casts — no profiling RPC was ever issued."""
    def _spanning():
        _keep_burning()
        prof = us.cluster_profile()
        roles = {w["role"] for w in prof.get("windows", ())}
        if {"head", "worker"} <= roles and len(_cluster_pids(prof)) >= 3:
            return prof
        return None

    prof = _wait(_spanning, timeout=60, msg="profile never spanned "
                 "head + 2 workers")
    assert prof["stats"]["windows_total"] >= 3
    # The workers' actual work is IN the merged flamegraph.
    joined = " ".join(k for w in prof["windows"]
                      for k in w["folded"])
    assert "_burn" in joined
    # The sampler pays for itself visibly: per-window cost is recorded
    # and bounded (duty 1.0 at 50 Hz here — still cheap).
    for w in prof["windows"]:
        wall = max(0.1, w["end"] - w["start"])
        assert w["sample_cost_s"] / wall < 0.25


def test_e2e_cluster_profile_filters(cluster):
    prof = _wait(lambda: us.cluster_profile(role="worker") or None,
                 timeout=30, msg="worker windows")
    assert prof["windows"]
    assert all(w["role"] == "worker" for w in prof["windows"])
    node = prof["windows"][0]["node"]
    by_node = us.cluster_profile(node=node)
    assert by_node["windows"]
    assert all(w["node"] == node for w in by_node["windows"])


def test_e2e_cli_renders_and_exports(cluster, tmp_path, capsys,
                                     monkeypatch):
    from ray_tpu import scripts

    monkeypatch.setattr(scripts, "_connect", lambda addr: None)

    def _args(**kw):
        base = dict(address="local", role=None, node=None, window=None,
                    diff=None, speedscope=None, output=None, top=15,
                    json=False)
        base.update(kw)
        return type("Args", (), base)()

    _wait(lambda: len(_cluster_pids(us.cluster_profile())) >= 3
          or (_keep_burning() or False), timeout=60, msg="pids")
    assert scripts.cmd_profile(_args()) == 0
    out = capsys.readouterr().out
    assert "cluster profile:" in out
    assert "top self-time frames" in out
    assert "top stacks:" in out

    # Collapsed-stack export (flamegraph.pl input) + speedscope.
    collapsed = tmp_path / "cluster.folded"
    speed = tmp_path / "cluster.speedscope.json"
    assert scripts.cmd_profile(
        _args(output=str(collapsed), speedscope=str(speed))) == 0
    lines = collapsed.read_text().splitlines()
    assert lines and all(ln.rsplit(" ", 1)[1].isdigit() for ln in lines)
    doc = json.loads(speed.read_text())
    assert doc["profiles"] and doc["shared"]["frames"]

    # --json dumps the raw merged table.
    capsys.readouterr()  # drain the export confirmations
    assert scripts.cmd_profile(_args(json=True)) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["windows"] and doc["stats"]["windows_total"] >= 1


def test_e2e_cli_diff_between_windows(cluster, capsys, monkeypatch):
    from ray_tpu import scripts

    monkeypatch.setattr(scripts, "_connect", lambda addr: None)

    def _two_windows():
        _keep_burning()
        wins = sorted({w["window"]
                       for w in us.cluster_profile()["windows"]})
        return wins if len(wins) >= 2 else None

    wins = _wait(_two_windows, timeout=60, msg="two windows")
    args = type("Args", (), dict(
        address="local", role=None, node=None, window=None,
        diff=[str(wins[0]), str(wins[-1])], speedscope=None,
        output=None, top=10, json=False))()
    assert scripts.cmd_profile(args) == 0
    out = capsys.readouterr().out
    assert "differential profile" in out
    assert f"window {wins[0]} -> {wins[-1]}" in out


def test_e2e_gil_exemplar_reaches_head(cluster):
    """A task whose wall time dwarfs its CPU time (blocked on I/O or a
    C call holding nothing) pins a GIL-starvation exemplar that ships
    with the window and lands in the head's bounded exemplar ring."""
    @ray_tpu.remote
    def starved_nap():
        time.sleep(0.8)  # wall 0.8s, cpu ~0
        return 1

    assert ray_tpu.get(starved_nap.remote()) == 1

    def _exemplar():
        for ex in us.cluster_profile().get("gil_exemplars", ()):
            if ex.get("name") and "starved_nap" in ex["name"]:
                return ex
        return None

    ex = _wait(_exemplar, timeout=30, msg="exemplar never shipped")
    assert ex["wall_s"] >= 0.5
    assert ex["cpu_s"] <= ex["wall_s"] * 0.25
    assert ex["role"] == "worker"


def test_e2e_metrics_exposition_and_stats_block(cluster):
    _wait(lambda: us.cluster_profile()["windows"] or None,
          timeout=30, msg="windows")
    stats = global_runtime().conn.call("runtime_stats", {}, timeout=10)
    prof = stats["profiling"]
    assert prof["windows"] >= 1 and prof["samples_total"] > 0
    assert prof["self_time"]  # per-role top-N leaf frames
    text = um.runtime_stats_text()
    for series in ("ray_tpu_profile_windows", "ray_tpu_profile_windows_total",
                   "ray_tpu_profile_samples_total",
                   "ray_tpu_profile_self_hits"):
        assert series in text, series
    assert 'ray_tpu_profile_self_hits{role="' in text


def test_e2e_dashboard_profiles_endpoint(cluster):
    import urllib.request

    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    def _get(port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.read().decode()

    _wait(lambda: us.cluster_profile()["windows"] or None,
          timeout=30, msg="windows")
    port = start_dashboard()
    try:
        doc = json.loads(_get(port, "/api/profiles"))
        assert doc["windows"] and doc["stats"]["windows_total"] >= 1
        filtered = json.loads(_get(port, "/api/profiles?role=worker"))
        assert all(w["role"] == "worker" for w in filtered["windows"])
        # The SPA drives the same API and carries the Profiles view.
        html = _get(port, "/")
        assert "/api/profiles" in html and "Profiles" in html
    finally:
        stop_dashboard()


def test_e2e_kill_switch_no_profile_fields(cluster):
    # With the plane disabled, report casts must ship without profile
    # fields — verified at the summary source (the cast builder guards
    # on report_summary() returning None when no sampler is armed).
    rt = global_runtime()
    assert rt is not None
    s = profplane.sampler()
    assert s is not None  # the cluster fixture armed this process
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("RAY_TPU_PROFILING_ENABLED", "0")
        assert profplane.arm("driver", "again") is None
