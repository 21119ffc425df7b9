"""GPT-2 124M LM training throughput on one TPU chip, tokens/sec/chip.

One process, one bare ``jax.jit(make_train_step)`` — it touches neither
``train/`` nor the runtime, so it is the floor the runtime's paths are
compared against, not a measurement of them. It needs a TPU and fails
without one: a number from a CPU is not a speed (run it through the chip
tool; ``chip_smoke.py`` is the end-to-end check). ROADMAP A0 replaces
this file with a ``workloads`` table of cells.

``BASELINE_TOKENS_PER_SEC_PER_CHIP`` is the GPU-parity bar derived from
first principles (BASELINE.md publishes no absolute number): 124M params
≈ 6·N ≈ 0.74 GFLOPs/token; an A100-class GPU at ~40% MFU sustains
≈ 1.6e14 FLOPs/s → ≈ 100k tokens/sec/device.

Prints ONE JSON line: metric, value, unit, vs_baseline, and the device
as jax reports it (platform, device_kind, device count).
"""

from __future__ import annotations

import json
import os
import time

BASELINE_TOKENS_PER_SEC_PER_CHIP = 100_000.0


def run_bench() -> dict:
    from ray_tpu._private import compile_cache

    # Before jax is imported: jax reads the variable itself.
    os.environ.setdefault(compile_cache.ENV_VAR,
                          compile_cache.compile_cache_dir())
    import jax

    from ray_tpu import models
    from ray_tpu.ops.optim import FusedClipAdamW

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU chip; jax found platform "
            f"{dev.platform!r} ({dev.device_kind}). Run it through the "
            f"chip tool.")

    # The configuration of the one number on record (v5e,
    # benchmarks/ab_results.jsonl line 3): unrolled layers, no remat,
    # chunked fused LM-head CE with the accuracy argmax off, fused
    # clip+AdamW. Not the model's defaults (ROADMAP C4).
    batch, seq, steps = 24, 1024, 10
    cfg = models.gpt2_small(max_seq_len=seq, remat=False, scan_layers=False,
                            loss_chunk=4096, ce_impl="fused",
                            ce_accuracy=False)
    opt = FusedClipAdamW(learning_rate=3e-4, weight_decay=0.1, clip_norm=1.0)
    state = models.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step = jax.jit(models.make_train_step(cfg, opt), donate_argnums=(0,))
    batch_d = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)}

    t0 = time.perf_counter()
    state, m = step(state, batch_d)
    float(m["loss"])  # a host fetch: the step has really finished
    compile_s = time.perf_counter() - t0
    for _ in range(2):
        state, m = step(state, batch_d)
    float(m["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch_d)
    float(m["loss"])
    dt = time.perf_counter() - t0

    tok_per_sec = batch * seq * steps / dt
    return {
        "metric": "gpt2_124m_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tok_per_sec / BASELINE_TOKENS_PER_SEC_PER_CHIP,
                             4),
        "first_step_s": round(compile_s, 2),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }


if __name__ == "__main__":
    print(json.dumps(run_bench()))
