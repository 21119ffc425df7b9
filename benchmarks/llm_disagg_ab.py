"""Monolithic vs disaggregated LLM serving A/B (LLM inference plane).

Drives the SAME completion workload (shared prompt prefix + unique
tails, short decodes) through two equal-chip deployments of the
paged-KV engine:

  mono   — build_openai_app, 2 colocated prefill+decode replicas: every
           replica interleaves admission prefill with decode steps, so
           a long prefill stalls the token cadence of every active
           sequence on that replica;
  disagg — build_disaggregated_app, 1 prefill + 1 decode replica: the
           decode pool resumes zero-copy KV handoffs (page install, no
           prefill programs at all), so its step loop only ever decodes
           — and the single prefill pool sees the whole prompt stream,
           concentrating the shared-prefix cache instead of splitting
           it across replicas.

Methodology (DistServe-style, the shape the ISSUE specifies): both
deployments get the SAME offered load — a fixed open-loop request rate
set to half the slower side's measured capacity — and the acceptance
row is **SLO goodput per chip**: completion tokens/s from requests that
finish within the latency SLO, divided by chips. A closed-loop
saturation run would instead measure raw capacity, where at toy scale
the mono side always wins (the model is so small that the handoff tax
dominates); goodput-under-SLO at equal offered load is what the
disaggregation literature actually claims and what a production SLO
cares about. Each side's saturation capacity (`capacity_tokens_per_s`,
from the closed-loop rehearsal) and latency percentiles are reported
alongside so nothing is hidden.

Equal chips (2 vs 1+1); `goodput_ratio` and `p99_ratio` are
printed, plus the handoff's own latency/bytes and the
prefix/page telemetry behind it.

Run (needs a live cluster when imported; standalone boots one):
  python benchmarks/llm_disagg_ab.py [--json]
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N_REQUESTS = int(os.environ.get("LLM_AB_REQUESTS", "32"))
N_CLIENTS = int(os.environ.get("LLM_AB_CLIENTS", "8"))
PROMPT_TOKENS = int(os.environ.get("LLM_AB_PROMPT_TOKENS", "96"))
PREFIX_TOKENS = int(os.environ.get("LLM_AB_PREFIX_TOKENS", "64"))
MAX_TOKENS = int(os.environ.get("LLM_AB_MAX_TOKENS", "6"))
# Latency SLO for goodput accounting and the fraction of the slower
# side's saturation capacity offered to BOTH sides (equal offered load,
# comfortably below either side's knee — goodput compares SLO
# attainment, not saturation throughput).
SLO_S = float(os.environ.get("LLM_AB_SLO_S", "0.5"))
RATE_FRACTION = float(os.environ.get("LLM_AB_RATE_FRACTION", "0.5"))


def _config():
    from ray_tpu.llm import LLMConfig, SamplingParams
    from ray_tpu.models import transformer as tfm

    return LLMConfig(
        model=tfm.tiny(vocab_size=512, max_seq_len=256),
        max_num_seqs=8,
        max_seq_len=128,
        prefill_buckets=(16, 32, 64, 128),
        kv_page_size=16,
        enable_prefix_caching=True,
        prefix_block=16,
        sampling_defaults=SamplingParams(max_tokens=MAX_TOKENS),
    )


def _prompts(n: int) -> list[str]:
    """Byte tokenizer: 1 token per char. Shared PREFIX_TOKENS-char head
    (page-aligned → COW page sharing), unique tails (every request still
    prefills something)."""
    prefix = ("ray tpu paged kv disaggregated serving shared prefix "
              * 8)[:PREFIX_TOKENS]
    width = max(1, PROMPT_TOKENS - PREFIX_TOKENS)
    return [prefix + f"q{i:03d} unique tail padding"[:width].ljust(width, ".")
            for i in range(n)]


def _closed_loop(handle, prompts: list[str], clients: int) -> dict:
    """N client threads drain a shared work queue; per-request latency +
    completion-token goodput."""
    work = list(enumerate(prompts))
    lat: list[float] = []
    tokens = [0]
    errors = [0]
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                if not work:
                    return
                _i, prompt = work.pop()
            t0 = time.perf_counter()
            try:
                r = handle.remote({"prompt": prompt,
                                   "max_tokens": MAX_TOKENS}).result(
                    timeout_s=300)
                dt = time.perf_counter() - t0
                with lock:
                    lat.append(dt)
                    tokens[0] += r["usage"]["completion_tokens"]
            except Exception:  # noqa: BLE001 — count, don't abort the A/B
                with lock:
                    errors[0] += 1

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(clients)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = max(time.time() - t0, 1e-6)
    lat.sort()

    def pct(q: float) -> "float | None":
        return (round(lat[min(len(lat) - 1, int(q * len(lat)))], 4)
                if lat else None)

    return {
        "requests": len(prompts),
        "ok": len(lat),
        "errors": errors[0],
        "wall_s": round(wall, 2),
        "completion_tokens": tokens[0],
        "tokens_per_s": round(tokens[0] / wall, 1),
        "p50_s": pct(0.5),
        "p99_s": pct(0.99),
    }


def _open_loop(handle, prompts: list[str], rate_hz: float,
               slo_s: float) -> dict:
    """Fire one request every 1/rate_hz seconds (equal offered load —
    the arrival clock never waits for completions), then score **SLO
    goodput**: completion tokens from requests that finished within
    slo_s, per second of wall time."""
    lat: list[float] = []
    toks_in_slo = [0]
    errors = [0]
    lock = threading.Lock()

    def fire(prompt: str):
        t0 = time.perf_counter()
        try:
            r = handle.remote({"prompt": prompt,
                               "max_tokens": MAX_TOKENS}).result(
                timeout_s=300)
            dt = time.perf_counter() - t0
            with lock:
                lat.append(dt)
                if dt <= slo_s:
                    toks_in_slo[0] += r["usage"]["completion_tokens"]
        except Exception:  # noqa: BLE001 — count, don't abort the A/B
            with lock:
                errors[0] += 1

    threads = []
    t0 = time.perf_counter()
    for i, prompt in enumerate(prompts):
        # sleep to the schedule, not by a fixed interval: late arrivals
        # don't shift the rest of the arrival process.
        delay = t0 + i / rate_hz - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=fire, args=(prompt,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    wall = max(time.perf_counter() - t0, 1e-6)
    lat.sort()

    def pct(q: float) -> "float | None":
        return (round(lat[min(len(lat) - 1, int(q * len(lat)))], 4)
                if lat else None)

    n = len(prompts)
    # Goodput is normalized by the OFFERED window (n/rate), not the
    # wall clock: both sides were given the same load over the same
    # window, and the wall clock's extra tail (the last request's own
    # latency) would penalize the higher-latency side twice — once in
    # attainment, once in the denominator.
    window = n / rate_hz
    return {
        "requests": n,
        "ok": len(lat),
        "errors": errors[0],
        "offered_rate_hz": round(rate_hz, 1),
        "wall_s": round(wall, 2),
        "slo_s": slo_s,
        "slo_attainment": round(
            sum(1 for d in lat if d <= slo_s) / max(n, 1), 3),
        "goodput_tokens_per_s": round(toks_in_slo[0] / window, 1),
        "p50_s": pct(0.5),
        "p99_s": pct(0.99),
    }


def _measure(handle, prompts: list[str], rate_hz: float,
             rounds: int = 2) -> dict:
    """Best-of-N open-loop rounds (by SLO goodput). One-off stalls (a
    lazy XLA compile on a first-hit path, CPU contention from a
    neighboring engine process) are ~0.7 s on a shared box — bigger
    than an entire round at quick sizing — so a single round can
    misread either side. Every steady-state path is warmed by the
    closed-loop rehearsal in run_ab; best-of-N reports the steady
    state, not the unluckiest stall."""
    best = None
    for _ in range(rounds):
        r = _open_loop(handle, prompts, rate_hz, SLO_S)
        if (best is None
                or r["goodput_tokens_per_s"] > best["goodput_tokens_per_s"]):
            best = r
    return best


class _PagePoller:
    """Samples peak KV-page pressure during a run (post-run the pools
    drain to ~0, so a single end snapshot would always read idle)."""

    def __init__(self, snap_fn):
        self._fn = snap_fn
        self.peak_in_use = 0
        self.total = 0
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.wait(0.15):
            try:
                kv = self._fn()
                self.peak_in_use = max(self.peak_in_use,
                                       int(kv.get("pages_in_use") or 0))
                self.total = int(kv.get("pages_total") or 0) or self.total
            except Exception:  # noqa: BLE001 — sampling is best-effort
                pass

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join(timeout=2)
        return False


def run_ab(n_requests: int = N_REQUESTS, clients: int = N_CLIENTS) -> dict:
    from ray_tpu import serve

    cfg = _config()
    prompts = _prompts(n_requests)
    out: dict = {"requests": n_requests, "clients": clients,
                 "prompt_tokens": PROMPT_TOKENS,
                 "prefix_tokens": PREFIX_TOKENS,
                 "max_tokens": MAX_TOKENS, "slo_s": SLO_S}

    # --- boot both equal-chip deployments -------------------------------
    from ray_tpu.llm import build_disaggregated_app, build_openai_app

    serve.run(build_openai_app(cfg, num_replicas=2, name="llm-ab-mono"),
              name="llm-ab-mono", proxy=False)
    serve.run(build_disaggregated_app(cfg, num_prefill=1, num_decode=1,
                                      name="llm-ab-disagg"),
              name="llm-ab-disagg", proxy=False)
    hm = serve.get_app_handle("llm-ab-mono")
    hd = serve.get_app_handle("llm-ab-disagg")
    for h in (hm, hd):
        for r in [h.remote({"prompt": p, "max_tokens": 2})
                  for p in prompts[:4]]:  # warm every replica's compiles
            r.result(timeout_s=600)

    # Closed-loop rehearsal on each side: warms every concurrent path
    # (prefix-hit prefill buckets, batch assembly at full client count)
    # AND measures saturation capacity, from which the shared offered
    # rate is derived — equal offered load, sized to the box.
    cap_mono = _closed_loop(hm, prompts, clients)
    cap_dis = _closed_loop(hd, prompts, clients)
    rate_hz = RATE_FRACTION * min(cap_mono["tokens_per_s"],
                                  cap_dis["tokens_per_s"]) / MAX_TOKENS
    rate_hz = max(rate_hz, 1.0)
    out["offered_rate_hz"] = round(rate_hz, 1)

    # --- monolithic: 2 colocated replicas (2 chips) ---------------------
    def _mono_kv():
        return hm.kv_snapshot.remote().result(timeout_s=30)["kv"]

    with _PagePoller(_mono_kv) as poll:
        out["mono"] = _measure(hm, prompts, rate_hz)
    out["mono"]["capacity_tokens_per_s"] = cap_mono["tokens_per_s"]
    out["mono"]["errors"] += cap_mono["errors"]
    kv = _mono_kv()
    out["mono"]["prefix_hit_rate"] = round(
        kv["prefix_hits"] / max(kv["prefix_queries"], 1), 3)
    out["mono"]["peak_page_utilization"] = round(
        poll.peak_in_use / max(poll.total, 1), 3)
    out["mono"]["chips"] = 2

    # --- disaggregated: 1 prefill + 1 decode (2 chips) ------------------
    def _disagg_kv():
        st = hd.stats.remote().result(timeout_s=30)
        return st["decode"]["kv"]

    with _PagePoller(_disagg_kv) as poll:
        out["disagg"] = _measure(hd, prompts, rate_hz)
    out["disagg"]["capacity_tokens_per_s"] = cap_dis["tokens_per_s"]
    out["disagg"]["errors"] += cap_dis["errors"]
    st = hd.stats.remote().result(timeout_s=60)
    pkv = st["prefill"]["kv"]
    out["disagg"]["prefix_hit_rate"] = round(
        pkv["prefix_hits"] / max(pkv["prefix_queries"], 1), 3)
    out["disagg"]["peak_page_utilization"] = round(
        poll.peak_in_use / max(poll.total, 1), 3)
    out["disagg"]["chips"] = 2
    out["handoff"] = {
        "count": st["handoff"]["count"],
        "bytes": st["handoff"]["bytes"],
        "p50_s": round(st["handoff"]["latency_p50_s"], 4),
        "p95_s": round(st["handoff"]["latency_p95_s"], 4),
    }
    serve.shutdown()

    # --- acceptance rows ------------------------------------------------
    # SLO goodput per chip at equal offered load (see module docstring).
    gp_mono = out["mono"]["goodput_tokens_per_s"] / out["mono"]["chips"]
    gp_dis = out["disagg"]["goodput_tokens_per_s"] / out["disagg"]["chips"]
    out["goodput_per_chip_mono"] = round(gp_mono, 2)
    out["goodput_per_chip_disagg"] = round(gp_dis, 2)
    out["goodput_ratio"] = round(gp_dis / max(gp_mono, 1e-9), 2)
    out["p99_ratio"] = round(
        out["disagg"]["p99_s"] / max(out["mono"]["p99_s"] or 1e-9, 1e-9), 2)
    return out


def main() -> None:
    # A CPU rehearsal at `tiny` by design (its rows are counts and
    # ratios, not device speeds): no chips in the
    # cluster, so the replicas place as chipless actors.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import ray_tpu

    ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4), num_tpus=0,
                 object_store_memory=256 * 1024 * 1024)
    try:
        results = run_ab()
    finally:
        ray_tpu.shutdown()
    if "--json" in sys.argv:
        print(json.dumps(results))
    else:
        print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
