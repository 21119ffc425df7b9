"""Core-op microbenchmarks.

Counterpart of the reference's microbenchmark
(reference: python/ray/_private/ray_perf.py:93 main() — timeit'd single/
multi client task throughput, actor calls, put/get, driven by
release/microbenchmark/run_microbenchmark.py). Run:

    python benchmarks/microbenchmark.py [--json]

Prints one line per op; --json emits a single JSON dict (the shape the
release pipeline records).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import ray_tpu


def timeit(name: str, fn, multiplier: int = 1, *, results: dict,
           min_time_s: float = 1.0) -> None:
    # Warmup pass, then measure whole-loop wall time (reference:
    # ray_perf.py timeit).
    fn()
    start = time.perf_counter()
    count = 0
    while time.perf_counter() - start < min_time_s:
        fn()
        count += 1
    dt = time.perf_counter() - start
    rate = count * multiplier / dt
    results[name] = rate
    print(f"{name}: {rate:,.0f} /s  (count={count} dt={dt:.2f}s)")


def main(as_json: bool = False) -> dict:
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                 log_to_driver=False)
    results: dict[str, float] = {}

    @ray_tpu.remote
    def small_task():
        return b"ok"

    # Warm the whole worker pool first (reference: ray_perf warms up
    # before timing) — otherwise the first timed wave measures worker
    # process spawn + import, not steady-state dispatch.
    ray_tpu.get([small_task.remote() for _ in range(64)])

    # single client task sync throughput
    timeit("single client tasks sync",
           lambda: ray_tpu.get(small_task.remote()), results=results)

    # batched async submission
    N = 100
    timeit("single client tasks async",
           lambda: ray_tpu.get([small_task.remote() for _ in range(N)]),
           N, results=results)

    # put/get small
    timeit("single client put sync",
           lambda: ray_tpu.put(b"x" * 100), results=results)
    ref_small = ray_tpu.put(b"y" * 100)
    timeit("single client get sync",
           lambda: ray_tpu.get(ref_small), results=results)

    # put/get 1 MiB numpy (zero-copy path)
    arr = np.random.rand(128, 1024)  # 1 MiB
    timeit("single client put 1MiB",
           lambda: ray_tpu.put(arr), results=results)
    ref_big = ray_tpu.put(arr)
    timeit("single client get 1MiB",
           lambda: ray_tpu.get(ref_big), results=results)

    # actor call throughput
    @ray_tpu.remote
    class Echo:
        def ping(self, x=None):
            return x

    actor = Echo.remote()
    timeit("single client actor calls sync",
           lambda: ray_tpu.get(actor.ping.remote()), results=results)
    timeit("single client actor calls async",
           lambda: ray_tpu.get([actor.ping.remote() for _ in range(N)]),
           N, results=results)

    # actor call pipelining: K calls in flight on the direct plane
    # (owner→worker window) before the barrier get — measures how much
    # the per-call overhead amortizes under pipeline depth. Depth 512
    # is the headline pipelined direct-plane number: past the
    # direct_window (64) calls queue owner-side, so this measures the
    # full submit→push→exec→seal loop at saturation.
    for depth in (8, 32, 512):
        timeit(f"single client actor pipeline depth {depth}",
               lambda d=depth: ray_tpu.get(
                   [actor.ping.remote() for _ in range(d)]),
               depth, results=results)

    # actor arg passing by reference
    timeit("actor calls with 1MiB arg (by ref)",
           lambda: ray_tpu.get(actor.ping.remote(ref_big)),
           results=results)

    # lease-cached same-shape task throughput (direct-call plane): after
    # the first submission mints a worker lease for the shape, same-shape
    # tasks dispatch owner→worker with zero head frames.
    @ray_tpu.remote
    def leased_task(i):
        return i

    ray_tpu.get([leased_task.remote(i) for i in range(8)])  # warm lease
    timeit("single client leased tasks sync",
           lambda: ray_tpu.get(leased_task.remote(1)), results=results)
    timeit("single client leased tasks async",
           lambda: ray_tpu.get([leased_task.remote(i) for i in range(N)]),
           N, results=results)

    ray_tpu.kill(actor)
    ray_tpu.shutdown()
    bench_data_plane(results)
    bench_wire_binary(results)
    bench_native_loop(results)
    bench_seal_coalescing(results)
    bench_event_overhead(results)
    bench_forensics_overhead(results)
    bench_admission_overhead(results)
    bench_deadline_overhead(results)
    bench_census_overhead(results)
    bench_trace_overhead(results)
    bench_profiling_overhead(results)
    bench_telemetry_overhead(results)
    if as_json:
        print(json.dumps({"microbenchmark": results}))
    return results


def bench_data_plane(results: dict) -> None:
    """Data-plane put/get throughput (PR 8): bulk numpy through the
    arena (put + the zero-copy get path) in GiB/s, and the colocated
    device-result cache for jax.Arrays (a cache hit costs a dict
    lookup, not a device→host→device round trip)."""
    import gc

    ray_tpu.init(num_cpus=2, object_store_memory=768 * 1024 * 1024,
                 log_to_driver=False)
    try:
        size = 16 << 20
        gib = size / float(1 << 30)
        arr = np.random.rand(size // 8)  # 16 MiB of float64

        def put_once():
            ray_tpu.put(arr)  # ref dies -> release flusher frees async

        timeit("put 16MiB numpy GiB/s", put_once, gib, results=results)
        gc.collect()
        ref = ray_tpu.put(arr)

        def get_once():
            v = ray_tpu.get(ref)
            assert v.shape == arr.shape

        timeit("get 16MiB numpy zero-copy GiB/s", get_once, gib,
               results=results)
        try:
            import jax.numpy as jnp

            jarr = jnp.asarray(arr)
            jref = ray_tpu.put(jarr)
            timeit("get 16MiB jax colocated GiB/s",
                   lambda: ray_tpu.get(jref), gib, results=results)
        except Exception:
            pass  # jax-free box: skip the device-cache op
    finally:
        ray_tpu.shutdown()


def bench_wire_binary(results: dict) -> None:
    """Binary hot-path wire format on/off (RAY_TPU_WIRE_BINARY —
    negotiated per connection at register/whoami, so flipping the env
    before init flips the whole cluster): pipelined direct actor calls
    and lease-cached task floods pay one pickle round trip per frame
    when OFF, the wirefmt.py compact frames when ON."""
    import os

    from ray_tpu._private import config as config_mod

    for mode in ("on", "off"):
        os.environ["RAY_TPU_WIRE_BINARY"] = "1" if mode == "on" else "0"
        config_mod.GLOBAL_CONFIG.wire_binary = (mode == "on")
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                     log_to_driver=False)

        @ray_tpu.remote
        class WEcho:
            def ping(self, x=None):
                return x

        actor = WEcho.remote()
        ray_tpu.get([actor.ping.remote() for _ in range(64)])  # warm
        timeit(f"actor pipeline depth 512 wire_binary {mode}",
               lambda: ray_tpu.get(
                   [actor.ping.remote() for _ in range(512)]),
               512, results=results)

        @ray_tpu.remote
        def wtask(i):
            return i

        N = 100
        ray_tpu.get([wtask.remote(i) for i in range(64)])  # warm leases
        timeit(f"tasks async wire_binary {mode}",
               lambda: ray_tpu.get([wtask.remote(i) for i in range(N)]),
               N, results=results)
        ray_tpu.kill(actor)
        ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_WIRE_BINARY", None)
    config_mod.GLOBAL_CONFIG.wire_binary = True


def bench_native_loop(results: dict) -> None:
    """Native C event-loop fast lane on/off (RAY_TPU_NATIVE_LOOP): the
    same depth-512 pipelined actor flood and leased-task flood, once
    through the C reader/flusher/ack-sink lane and once through the
    pure-Python loops. Skipped (recorded as the literal string
    "unavailable") when the box cannot build _evloop.so — then both
    modes would measure the identical Python lane."""
    import os

    from ray_tpu._private import config as config_mod, evloop

    if evloop.module() is None:
        results["native_loop"] = "unavailable"
        return
    for mode in ("on", "off"):
        os.environ["RAY_TPU_NATIVE_LOOP"] = "1" if mode == "on" else "0"
        config_mod.GLOBAL_CONFIG.native_loop = (mode == "on")
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                     log_to_driver=False)

        @ray_tpu.remote
        class NEcho:
            def ping(self, x=None):
                return x

        actor = NEcho.remote()
        ray_tpu.get([actor.ping.remote() for _ in range(64)])  # warm
        timeit(f"actor pipeline depth 512 native_loop {mode}",
               lambda: ray_tpu.get(
                   [actor.ping.remote() for _ in range(512)]),
               512, results=results)

        @ray_tpu.remote
        def ntask(i):
            return i

        N = 100
        ray_tpu.get([ntask.remote(i) for i in range(64)])  # warm leases
        timeit(f"tasks async native_loop {mode}",
               lambda: ray_tpu.get([ntask.remote(i) for i in range(N)]),
               N, results=results)
        ray_tpu.kill(actor)
        ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_NATIVE_LOOP", None)
    config_mod.GLOBAL_CONFIG.native_loop = True


def bench_seal_coalescing(results: dict) -> None:
    """Seal/ack coalescing on/off (RAY_TPU_WIRE_COALESCE): with it OFF
    every buffered ack/seal pays its own record framing inside the
    cast batch; ON merges consecutive same-kind records into one frame
    body (rpc.Connection.flush_casts)."""
    import os

    from ray_tpu._private import config as config_mod

    for mode in ("on", "off"):
        os.environ["RAY_TPU_WIRE_COALESCE"] = "1" if mode == "on" else "0"
        config_mod.GLOBAL_CONFIG.wire_coalesce = (mode == "on")
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                     log_to_driver=False)

        @ray_tpu.remote
        class CEcho:
            def ping(self, x=None):
                return x

        actor = CEcho.remote()
        ray_tpu.get([actor.ping.remote() for _ in range(64)])  # warm
        timeit(f"actor pipeline depth 512 seal_coalescing {mode}",
               lambda: ray_tpu.get(
                   [actor.ping.remote() for _ in range(512)]),
               512, results=results)
        ray_tpu.kill(actor)
        ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_WIRE_COALESCE", None)
    config_mod.GLOBAL_CONFIG.wire_coalesce = True


def bench_admission_overhead(results: dict) -> None:
    """Admission-gate overhead: the owner-side gate is a pending-set
    size check per submit and the head gate two dict lookups — with
    default budgets (never tripping) the on/off delta must be within
    run noise (±5%, the CI guard for "admission control is free on the
    healthy path"). "off" disables both budgets entirely."""
    from ray_tpu._private import config as config_mod

    for mode in ("on", "off"):
        cfg = config_mod.GLOBAL_CONFIG
        saved = (cfg.admission_max_pending_per_owner,
                 cfg.admission_max_pending_total)
        if mode == "off":
            cfg.admission_max_pending_per_owner = 0
            cfg.admission_max_pending_total = 0
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                     log_to_driver=False,
                     _system_config=(
                         {} if mode == "on"
                         else {"admission_max_pending_per_owner": 0,
                               "admission_max_pending_total": 0}))

        @ray_tpu.remote
        def adm(i):
            return i

        N = 100
        ray_tpu.get([adm.remote(i) for i in range(64)])  # warm
        timeit(f"tasks async admission {mode}",
               lambda: ray_tpu.get([adm.remote(i) for i in range(N)]),
               N, results=results)
        ray_tpu.shutdown()
        (cfg.admission_max_pending_per_owner,
         cfg.admission_max_pending_total) = saved


def bench_deadline_overhead(results: dict) -> None:
    """Deadline-stamping overhead: .options(timeout_s=...) costs one
    time.time() at submit, one optional trailing field in the compiled
    spec encoding, and a float comparison at each queue hop. Generous
    deadlines never shed, so the delta vs unstamped tasks must be
    within run noise (±5%)."""
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                 log_to_driver=False)

    @ray_tpu.remote
    def dl(i):
        return i

    N = 100
    ray_tpu.get([dl.remote(i) for i in range(64)])  # warm
    timeit("tasks async deadline off",
           lambda: ray_tpu.get([dl.remote(i) for i in range(N)]),
           N, results=results)
    stamped = dl.options(timeout_s=3600.0)
    timeit("tasks async deadline on",
           lambda: ray_tpu.get([stamped.remote(i) for i in range(N)]),
           N, results=results)
    ray_tpu.shutdown()


def bench_census_overhead(results: dict) -> None:
    """Object-census overhead (RAY_TPU_OBJECT_CENSUS_ENABLED): the
    steady-state cost is one interned-callsite lookup + a dict write
    per put/submit and a dict pop per ref release — the summary ships
    piggybacked on the amortized rpc_report cast, never per call. The
    on/off delta across task floods and put loops must be within run
    noise (±5%, the CI guard for "the census is steady-state free")."""
    import os

    from ray_tpu._private import config as config_mod

    for mode in ("on", "off"):
        os.environ["RAY_TPU_OBJECT_CENSUS_ENABLED"] = (
            "1" if mode == "on" else "0")
        config_mod.GLOBAL_CONFIG.object_census_enabled = (mode == "on")
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                     log_to_driver=False)

        @ray_tpu.remote
        def ctask(i):
            return i

        N = 100
        ray_tpu.get([ctask.remote(i) for i in range(64)])  # warm leases
        timeit(f"tasks async census {mode}",
               lambda: ray_tpu.get([ctask.remote(i) for i in range(N)]),
               N, results=results)
        timeit(f"put sync census {mode}",
               lambda: ray_tpu.put(b"x" * 100), results=results)
        ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_OBJECT_CENSUS_ENABLED", None)
    config_mod.GLOBAL_CONFIG.object_census_enabled = True


def bench_event_overhead(results: dict) -> None:
    """Flight-recorder overhead: pipelined direct actor calls with the
    tracing plane on vs off (RAY_TPU_TASK_EVENTS_ENABLED — inherited by
    spawned workers, so the whole cluster flips). Events ride existing
    messages, so the delta is the stamping cost (a few time.time()
    calls and dict writes per task), not extra frames."""
    import os

    from ray_tpu._private import config as config_mod

    for mode in ("on", "off"):
        # Env var: spawned workers and the head's fresh Config pick it
        # up; the in-place mutation flips the driver-side stamping
        # (modules bound GLOBAL_CONFIG by reference at import).
        os.environ["RAY_TPU_TASK_EVENTS_ENABLED"] = (
            "1" if mode == "on" else "0")
        config_mod.GLOBAL_CONFIG.task_events_enabled = (mode == "on")
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                     log_to_driver=False)

        @ray_tpu.remote
        class EvEcho:
            def ping(self, x=None):
                return x

        actor = EvEcho.remote()
        ray_tpu.get([actor.ping.remote() for _ in range(64)])  # warm
        timeit(f"actor pipeline depth 32 events {mode}",
               lambda: ray_tpu.get(
                   [actor.ping.remote() for _ in range(32)]),
               32, results=results)
        ray_tpu.kill(actor)
        ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_TASK_EVENTS_ENABLED", None)
    config_mod.GLOBAL_CONFIG.task_events_enabled = True


def bench_forensics_overhead(results: dict) -> None:
    """Crash-forensics overhead: pipelined direct actor calls with the
    post-mortem plane on vs off (RAY_TPU_CRASH_FORENSICS_ENABLED —
    workers read it at boot). Arming is one-time; the steady-state cost
    is the per-task beacon stamp (an mmap slice write), so the on/off
    delta must be within noise — the CI guard for "forensics is
    steady-state free"."""
    import os

    from ray_tpu._private import config as config_mod

    for mode in ("on", "off"):
        os.environ["RAY_TPU_CRASH_FORENSICS_ENABLED"] = (
            "1" if mode == "on" else "0")
        config_mod.GLOBAL_CONFIG.crash_forensics_enabled = (mode == "on")
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                     log_to_driver=False)

        @ray_tpu.remote
        class FxEcho:
            def ping(self, x=None):
                return x

        actor = FxEcho.remote()
        ray_tpu.get([actor.ping.remote() for _ in range(64)])  # warm
        timeit(f"actor pipeline depth 32 forensics {mode}",
               lambda: ray_tpu.get(
                   [actor.ping.remote() for _ in range(32)]),
               32, results=results)
        ray_tpu.kill(actor)
        ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_CRASH_FORENSICS_ENABLED", None)
    config_mod.GLOBAL_CONFIG.crash_forensics_enabled = True


def bench_trace_overhead(results: dict) -> None:
    """Request-tracing overhead: pipelined direct actor calls with a
    sampled trace context ambient on every call (sample rate 1.0 — the
    worst case: every spec carries the trailing trace field and every
    task emits a span on its existing task_finished cast) vs the trace
    plane disabled (RAY_TPU_TRACE_ENABLED=0 — specs byte-identical to
    the pre-tracing wire format). Spans ride amortized casts, so the
    on/off delta must be within run noise (±5%) — the CI guard for
    "tracing is steady-state free"."""
    import os

    from ray_tpu._private import config as config_mod
    from ray_tpu._private import traceplane, worker_context

    for mode in ("on", "off"):
        os.environ["RAY_TPU_TRACE_ENABLED"] = "1" if mode == "on" else "0"
        config_mod.GLOBAL_CONFIG.trace_enabled = (mode == "on")
        config_mod.GLOBAL_CONFIG.trace_sample_rate = 1.0
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                     log_to_driver=False)

        @ray_tpu.remote
        class TrEcho:
            def ping(self, x=None):
                return x

        actor = TrEcho.remote()
        ray_tpu.get([actor.ping.remote() for _ in range(64)])  # warm
        ctx = traceplane.mint_trace("bench-trace") if mode == "on" else None
        tok = worker_context.push_trace_context(ctx) if ctx else None
        try:
            timeit(f"actor pipeline depth 32 tracing {mode}",
                   lambda: ray_tpu.get(
                       [actor.ping.remote() for _ in range(32)]),
                   32, results=results)
        finally:
            if tok is not None:
                worker_context.pop_trace_context(tok)
        ray_tpu.kill(actor)
        ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_TRACE_ENABLED", None)
    config_mod.GLOBAL_CONFIG.trace_enabled = True


def bench_profiling_overhead(results: dict) -> None:
    """Continuous-profiling overhead: pipelined direct actor calls with
    the always-on sampler armed in every process (RAY_TPU_PROFILING_ENABLED
    — workers read it at boot, the driver re-arms per mode) vs disarmed.
    The sampler is duty-cycled (default 19 Hz for 20% of each second) and
    window summaries ride the existing amortized rpc_report casts, so the
    on/off delta must stay ≤3% — the CI guard for "profiling is always-on
    affordable"."""
    import os

    from ray_tpu._private import profplane

    for mode in ("on", "off"):
        os.environ["RAY_TPU_PROFILING_ENABLED"] = "1" if mode == "on" else "0"
        profplane.disarm()  # arm() is per-process-global; reset per mode
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                     log_to_driver=False)

        @ray_tpu.remote
        class PfEcho:
            def ping(self, x=None):
                return x

        actor = PfEcho.remote()
        ray_tpu.get([actor.ping.remote() for _ in range(64)])  # warm
        timeit(f"actor pipeline depth 32 profiling {mode}",
               lambda: ray_tpu.get(
                   [actor.ping.remote() for _ in range(32)]),
               32, results=results)
        ray_tpu.kill(actor)
        ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_PROFILING_ENABLED", None)
    profplane.disarm()


def bench_telemetry_overhead(results: dict) -> None:
    """Telemetry-history + alert-engine overhead (RAY_TPU_TSDB_ENABLED /
    RAY_TPU_ALERTS_ENABLED): pipelined direct actor calls with the
    head's tsdb sweep and SLO rule evaluation running at an aggressive
    cadence vs both planes killed. The sweep samples head tables on the
    health tick and rules read bounded ring buffers — no per-call work
    anywhere — so the on/off delta must stay ≤3%. Single boots swing
    >2x on a loaded shared box, so this interleaves on/off pairs and
    reports the per-mode MEDIAN plus the ratio — the committed number
    CI compares against."""
    import os
    import statistics

    samples: dict[str, list] = {"on": [], "off": []}
    for _round in range(3):
        for mode in ("on", "off"):
            flag = "1" if mode == "on" else "0"
            os.environ["RAY_TPU_TSDB_ENABLED"] = flag
            os.environ["RAY_TPU_ALERTS_ENABLED"] = flag
            ray_tpu.init(
                num_cpus=4, object_store_memory=256 * 1024 * 1024,
                log_to_driver=False,
                _system_config={"health_check_period_s": 0.2,
                                "tsdb_sample_interval_s": 0.25,
                                "alerts_eval_interval_s": 0.25})

            @ray_tpu.remote
            class TsEcho:
                def ping(self, x=None):
                    return x

            actor = TsEcho.remote()
            ray_tpu.get([actor.ping.remote() for _ in range(64)])
            scratch: dict[str, float] = {}
            timeit(f"telemetry {mode} round {_round}",
                   lambda: ray_tpu.get(
                       [actor.ping.remote() for _ in range(32)]),
                   32, results=scratch)
            samples[mode].append(scratch[f"telemetry {mode} round "
                                         f"{_round}"])
            ray_tpu.kill(actor)
            ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_TSDB_ENABLED", None)
    os.environ.pop("RAY_TPU_ALERTS_ENABLED", None)
    for mode in ("on", "off"):
        results[f"actor pipeline depth 32 telemetry {mode}"] = \
            statistics.median(samples[mode])
    results["telemetry on/off median ratio"] = round(
        results["actor pipeline depth 32 telemetry on"]
        / results["actor pipeline depth 32 telemetry off"], 4)


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--json", action="store_true")
    args = p.parse_args()
    main(as_json=args.json)
