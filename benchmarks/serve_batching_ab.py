"""Continuous vs fixed-flush batching A/B (serving plane).

Drives the SAME arrival process through two batch schedulers over an
identical simulated batch-exec function (latency = base + per_item *
batch_size, concurrency-tolerant — the TPU-forward-pass shape):

  continuous — serve/scheduler.ContinuousBatcher: batches assemble and
               launch while earlier batches still execute (no drain
               barrier), size picked under the latency SLO;
  fixed      — the legacy one-shot flusher: collect up to
               max_batch_size (or the wait timeout), execute, WAIT for
               the batch to finish, repeat. The drain barrier means the
               executor idles during every assembly window and vice
               versa.

At equal offered load the continuous scheduler should finish the run
faster (higher throughput) at equal-or-better p99 — that delta is the
row `speedup`.

Run: python benchmarks/serve_batching_ab.py [--json]
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N_REQUESTS = int(os.environ.get("AB_REQUESTS", "400"))
INTERARRIVAL_S = float(os.environ.get("AB_INTERARRIVAL_S", "0.002"))
MAX_BATCH = 8
BATCH_WAIT_S = 0.004
EXEC_BASE_S = 0.010
EXEC_PER_ITEM_S = 0.002
SLO_S = 0.25


async def _exec(items: list) -> list:
    await asyncio.sleep(EXEC_BASE_S + EXEC_PER_ITEM_S * len(items))
    return items


class FixedFlusher:
    """The legacy design: one batch in flight at a time (drain
    barrier); submissions queue while the current batch executes."""

    def __init__(self, fn, max_batch_size: int, wait_s: float):
        self._fn = fn
        self._max = max_batch_size
        self._wait = wait_s
        self._queue: asyncio.Queue = asyncio.Queue()
        self._task: "asyncio.Task | None" = None

    def submit(self, item):
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._queue.put_nowait((item, fut))
        if self._task is None or self._task.done():
            self._task = loop.create_task(self._run())
        return fut

    async def _run(self):
        while not self._queue.empty():
            batch = [self._queue.get_nowait()]
            deadline = asyncio.get_running_loop().time() + self._wait
            while len(batch) < self._max:
                remaining = deadline - asyncio.get_running_loop().time()
                if remaining <= 0:
                    break
                try:
                    batch.append(await asyncio.wait_for(
                        self._queue.get(), remaining))
                except asyncio.TimeoutError:
                    break
            results = await self._fn([b[0] for b in batch])  # barrier
            for (_item, fut), r in zip(batch, results):
                if not fut.done():
                    fut.set_result(r)


async def _drive(submit) -> dict:
    """Offer N_REQUESTS at a fixed interarrival; measure per-request
    latency and end-to-end wall time."""
    lat: list = []
    done = asyncio.Event()
    remaining = [N_REQUESTS]

    def _finish(t0, fut):
        lat.append(time.perf_counter() - t0)
        remaining[0] -= 1
        if remaining[0] == 0:
            done.set()

    t_start = time.perf_counter()
    for i in range(N_REQUESTS):
        t0 = time.perf_counter()
        fut = submit(i)
        fut.add_done_callback(lambda f, t0=t0: _finish(t0, f))
        await asyncio.sleep(INTERARRIVAL_S)
    await done.wait()
    wall = time.perf_counter() - t_start
    lat.sort()

    def pct(q: float) -> float:
        return lat[min(len(lat) - 1, int(q * len(lat)))]

    return {
        "requests": N_REQUESTS,
        "wall_s": round(wall, 3),
        "tput_rps": round(N_REQUESTS / wall, 1),
        "p50_ms": round(pct(0.5) * 1e3, 2),
        "p99_ms": round(pct(0.99) * 1e3, 2),
    }


async def _run_ab() -> dict:
    from ray_tpu.serve.scheduler import ContinuousBatcher

    cont = ContinuousBatcher(
        _exec, max_batch_size=MAX_BATCH, batch_wait_timeout_s=BATCH_WAIT_S,
        target_latency_slo_s=SLO_S)
    continuous = await _drive(cont.submit)
    continuous["batches"] = cont.stats["batches"]
    cont.shutdown()

    fixed_b = FixedFlusher(_exec, MAX_BATCH, BATCH_WAIT_S)
    fixed = await _drive(fixed_b.submit)

    return {
        "continuous": continuous,
        "fixed": fixed,
        "speedup": round(continuous["tput_rps"] / fixed["tput_rps"], 2),
        "p99_ratio": round(continuous["p99_ms"] / fixed["p99_ms"], 2),
    }


def run_ab() -> dict:
    return asyncio.run(_run_ab())


def main() -> None:
    results = run_ab()
    if "--json" in sys.argv:
        print(json.dumps(results))
    else:
        print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
