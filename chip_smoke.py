"""The quickest proof that the system still starts on the chip.

Drives both user-facing paths once, through their public entry points,
at the full width of GPT-2 124M (``models.gpt2_small()``: 12 layers x
768 x 12 heads, vocab 50,304, bf16 activations), on every chip
``ray_tpu.init()`` detects:

  train   JaxTrainer.fit(): one mesh worker over all chips, a seeded
          ray_tpu.data token pipeline, 2 warm-up + 5 steps at 8 x 1024
          tokens per chip, one checkpointed train.report().
  kernel  in a chip-holding task: attention(impl="auto") forward and
          gradient at [4, 2048, 12, 64] bf16 against
          dot_product_attention, the lowered text carrying
          tpu_custom_call; then one make_train_step at T=2048.
  delta_rule  in a chip-holding task: the chunked gated delta rule
          (ops/linear_attention.py) at [1, 16384, 32, 128] bf16, forward
          and gradient timed, then both against the token-by-token
          recurrence on a 2,048-token prefix.
  afmoe_step  in a chip-holding task: Trinity-Mini's block
          (models.trinity_mini_26b_a3b: gated attention, a head's q and k
          normed, window 2,048 and global NoPE layers behind a dense one,
          four norms a block, 8 of 128 experts held) at published widths,
          published layers 1 to 3, one row of 4,096: its logits against
          chipbench/reference/afmoe.py, then one make_train_step.
  serve   serve.run(build_openai_app(...)), one one-chip replica per
          chip, concurrent POST /v1/completions through the proxy port,
          one /v1/chat/completions; each replica reports what it ran on.

This process never initializes a jax backend: the runtime's workers hold
the chips, and a parent that touched jax would take them first. There is
no CPU mode — on a machine with no chip the script says so and exits
non-zero (the rehearsal at `tiny` size is tests/test_chip_smoke.py). Any
failed phase ends the run with a non-zero exit naming the phase.

The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
with the device as jax reports it inside the chip-holding workers.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import json
import math
import os
import signal
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

# What the script runs. FULL is the only size main() uses; TINY is the
# CPU rehearsal the test suite drives through the same phase functions.
FULL = dict(
    name="full",
    model="gpt2_small", model_kwargs={},      # the defaults users get
    seq=1024, rows_per_chip=8, warmup=2, steps=5,
    kernel_shape=(4, 2048, 12, 64), kernel_batch=4,
    kernel_timeout_s=420.0,
    # the gated delta rule at train-kimilinear-ep32share's shape
    delta_shape=(1, 16384, 32, 128), delta_prefix=2048,
    # train-trinitymini-ep16share's block, three of its five layers (a
    # dense sliding one, a sliding and a full expert layer), two windows
    afmoe=dict(n_layers=3, first_layer=1, n_dense_layers=1, vocab_size=25024,
               experts_held=(0, 16)), afmoe_seq=4096,
    serve_model="gpt2_small", serve_slots=8, serve_seq=1024,
    n_requests=8, prompt_tokens=100, max_tokens=32,
    request_timeout_s=300.0,
)
TINY = dict(
    name="tiny",
    model="tiny", model_kwargs={},
    seq=32, rows_per_chip=2, warmup=1, steps=2,
    kernel_shape=(2, 64, 2, 16), kernel_batch=2,
    kernel_timeout_s=180.0,
    delta_shape=(1, 192, 2, 16), delta_prefix=128,
    afmoe=dict(n_layers=3, first_layer=1, n_dense_layers=1, d_model=64,
               n_heads=4, n_kv_heads=2, d_head=16, d_ff=32, d_ff_dense=32,
               d_ff_shared=32, n_experts=8, expert_top_k=3, vocab_size=256,
               sliding_window=16, embed_scale=8.0, experts_held=(1, 2),
               dtype="float32"), afmoe_seq=64,
    serve_model="tiny", serve_slots=4, serve_seq=64,
    n_requests=4, prompt_tokens=20, max_tokens=4,
    request_timeout_s=120.0,
)
SEED = 0
# The driver allows 1200 s, compilation included.
BUDGET_S = 1100.0


class PhaseFailed(Exception):
    def __init__(self, phase: str):
        super().__init__(phase)
        self.phase = phase


@contextlib.contextmanager
def phase(name: str, walls: dict):
    """Time one phase; any exception inside ends the run under its name."""
    print(f"[chip_smoke] phase {name} ...", flush=True)
    t0 = time.time()
    walls["running"] = name
    try:
        yield
    except PhaseFailed:
        raise
    except Exception as e:  # noqa: BLE001 — re-raised under the phase's name
        raise PhaseFailed(name) from e
    del walls["running"]
    walls[name] = round(time.time() - t0, 2)
    print(f"[chip_smoke] phase {name} ok in {walls[name]}s", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def show(name: str, report: dict) -> None:
    """A phase's raw report, before it is checked: a failed check still
    leaves what the workers measured in the output."""
    print(f"[chip_smoke] {name} report: {json.dumps(report)}", flush=True)


# ---------------------------------------------------------------------------
# train


def make_token_rows(batch: dict, *, seq: int, vocab: int) -> dict:
    """map_batches UDF: row ids -> seeded token rows [n, seq + 1]. Seeded
    per row, so the data does not depend on how blocks were cut."""
    import numpy as np

    rows = [np.random.default_rng(SEED + int(i)).integers(
        0, vocab, size=seq + 1, dtype=np.int32) for i in batch["id"]]
    return {"tokens": np.stack(rows)}


def train_loop(config: dict) -> None:
    """The per-worker loop JaxTrainer runs in the chip-holding worker."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu import models, train
    from ray_tpu.parallel import (MeshConfig, batch_sharding,
                                  infer_param_specs, make_shardings)

    devices = jax.devices()
    mesh_devices = devices[:config["chips"]]
    n = len(mesh_devices)
    cfg = getattr(models, config["model"])(**config["model_kwargs"])
    # ZeRO-3 over every chip (collapses to one device on one chip): each
    # device holds a shard of the parameters and of the batch.
    mesh = MeshConfig(data=1, fsdp=-1).build(mesh_devices)
    opt = optax.adamw(3e-4, weight_decay=0.1)
    params = models.init_params(jax.random.PRNGKey(config["seed"]), cfg)
    shardings = make_shardings(mesh, infer_param_specs(
        params, mesh, models.partition_specs(cfg)))
    params = jax.tree.map(jax.device_put, params, shardings)
    replicated = NamedSharding(mesh, PartitionSpec())

    def on_mesh(x):
        # The moments follow the parameters' shardings; the optimizer's
        # scalars (its step count) come out on one device, uncommitted.
        # Commit every leaf where it belongs, or the second step sees
        # different inputs from the first and compiles again.
        return jax.device_put(x, x.sharding if len(x.sharding.device_set) == n
                              else replicated)

    state = {
        "params": params,
        "opt_state": jax.tree.map(on_mesh, jax.jit(opt.init)(params)),
        "step": jax.device_put(jnp.zeros((), jnp.int32), replicated),
    }
    state_shardings = jax.tree.map(lambda x: x.sharding, state)
    step = jax.jit(models.make_train_step(cfg, opt, mesh=mesh),
                   donate_argnums=(0,),
                   out_shardings=(state_shardings, None))

    rows = config["rows_per_chip"] * n
    batches = train.get_dataset_shard("train").iter_jax_batches(
        batch_size=rows, sharding=batch_sharding(mesh))
    losses, step_s, input_s = [], [], []
    batch_devices = 0
    t_in = time.perf_counter()
    for batch in batches:
        input_s.append(time.perf_counter() - t_in)
        batch_devices = len(batch["tokens"].sharding.device_set)
        t0 = time.perf_counter()
        state, metrics = step(state, {"tokens": batch["tokens"]})
        losses.append(float(metrics["loss"]))  # host fetch: step is done
        step_s.append(time.perf_counter() - t0)
        t_in = time.perf_counter()

    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    ckpt = train.Checkpoint.from_directory(ckpt_dir)
    t0 = time.perf_counter()
    ckpt.save_pytree(state["params"], name="params")
    save_s = time.perf_counter() - t0

    w = config["warmup"]
    mem = [d.memory_stats() or {} for d in mesh_devices]
    train.report({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": dict(mesh.shape),
        "chips_env": os.environ.get("TPU_VISIBLE_CHIPS"),
        "n_params": cfg.num_params(),
        "vocab_size": cfg.vocab_size,
        "rows": rows, "seq": config["seq"],
        "losses": losses,
        "first_step_s": round(step_s[0], 3),           # compile included
        "step_s": [round(s, 4) for s in step_s[w:]],
        "input_wait_s": [round(s, 4) for s in input_s[w:]],
        "compiles": step._cache_size(),
        "checkpoint_save_s": round(save_s, 3),
        "param_devices_min": min(
            len(p.sharding.device_set)
            for p in jax.tree.leaves(state["params"])),
        "batch_devices": batch_devices,
        "bytes_in_use": [m.get("bytes_in_use") for m in mem],
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
    }, checkpoint=ckpt)


def train_phase(size: dict, chips: int, platform: str, storage: str) -> dict:
    import ray_tpu.data
    from ray_tpu import models
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    n_batches = size["warmup"] + size["steps"]
    n_rows = n_batches * size["rows_per_chip"] * chips
    vocab = getattr(models.transformer, size["model"])(
        **size["model_kwargs"]).vocab_size
    import functools

    ds = ray_tpu.data.range(n_rows).map_batches(functools.partial(
        make_token_rows, seq=size["seq"], vocab=vocab))
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "model": size["model"], "model_kwargs": size["model_kwargs"],
            "seq": size["seq"], "rows_per_chip": size["rows_per_chip"],
            "warmup": size["warmup"], "chips": chips, "seed": SEED},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, tpus_per_worker=chips,
            topology="mesh"),
        datasets={"train": ds},
        run_config=RunConfig(name="chip_smoke", storage_path=storage),
    ).fit()
    r = dict(result.metrics)
    r["checkpoint"] = result.checkpoint.path if result.checkpoint else None
    show("train", r)

    require(r["platform"] == platform,
            f"train worker ran on {r['platform']!r}, expected {platform!r}")
    if platform == "tpu":
        require(r["device_count"] == chips,
                f"train worker saw {r['device_count']} devices, "
                f"leased {chips} chips")
    losses = r["losses"]
    require(len(losses) == n_batches, f"{len(losses)} steps of {n_batches}")
    require(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    require(len(set(losses)) > 1, f"every loss equal: {losses}")
    # Seeded N(0, 0.02) weights put the first loss at ln(vocab).
    require(abs(losses[0] - math.log(r["vocab_size"])) < 0.5,
            f"first loss {losses[0]} far from ln(vocab) "
            f"{math.log(r['vocab_size']):.3f}")
    require(r["compiles"] == 1, f"train step compiled {r['compiles']} times")
    require(r["checkpoint"] and os.path.isdir(
        os.path.join(r["checkpoint"], "params")),
        f"Result.checkpoint missing: {r['checkpoint']}")
    if chips > 1:
        # Code that has only run on one device may put everything on
        # the first.
        require(r["param_devices_min"] == chips,
                f"a parameter lives on {r['param_devices_min']} of "
                f"{chips} devices")
        require(r["batch_devices"] == chips,
                f"the batch lives on {r['batch_devices']} of {chips} devices")
        if platform == "tpu":
            require(all(b and b > 0 for b in r["bytes_in_use"]),
                    f"a device holds nothing: {r['bytes_in_use']}")
    return r


# ---------------------------------------------------------------------------
# kernel


def kernel_body(size: dict) -> dict:
    """Runs in a one-chip worker: the Pallas kernels against the
    reference, then one whole train step that routes through them."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import models
    from ray_tpu.ops.attention import attention, dot_product_attention

    dev = jax.devices()[0]
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "device_count": len(jax.devices()),
           "chips_env": os.environ.get("TPU_VISIBLE_CHIPS"),
           "shape": list(size["kernel_shape"])}
    b, t, h, d = size["kernel_shape"]
    q, k, v, g = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
                  for kk in jax.random.split(jax.random.PRNGKey(SEED), 4))

    def auto(q, k, v):
        return attention(q, k, v, impl="auto")

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                * g.astype(jnp.float32)).sum()

    def timed_compile(fn, *args):
        lowered = jax.jit(fn).lower(*args)
        calls = lowered.as_text().count("tpu_custom_call")
        t0 = time.perf_counter()
        compiled = lowered.compile()
        return compiled, calls, round(time.perf_counter() - t0, 2)

    def err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.abs(a - b).max()), float(jnp.abs(b).max())

    fwd, out["fwd_custom_calls"], out["fwd_compile_s"] = timed_compile(
        auto, q, k, v)
    o = jax.block_until_ready(fwd(q, k, v))
    t0 = time.perf_counter()
    o = jax.block_until_ready(fwd(q, k, v))
    out["fwd_run_s"] = round(time.perf_counter() - t0, 5)
    errs = {"out": err(o, jax.jit(dot_product_attention)(q, k, v))}

    bwd, out["bwd_custom_calls"], out["bwd_compile_s"] = timed_compile(
        jax.grad(loss(auto), argnums=(0, 1, 2)), q, k, v)
    grads = jax.block_until_ready(bwd(q, k, v))
    t0 = time.perf_counter()
    grads = jax.block_until_ready(bwd(q, k, v))
    out["bwd_run_s"] = round(time.perf_counter() - t0, 5)
    refs = jax.jit(jax.grad(loss(dot_product_attention),
                            argnums=(0, 1, 2)))(q, k, v)
    for name, a, r in zip(("dq", "dk", "dv"), grads, refs):
        errs[name] = err(a, r)
    # Both sides round to bf16 after accumulating in different orders:
    # allow four bf16 ulps (2^-8 each) at the reference's largest value.
    out["errors"] = {
        name: {"max_abs_err": e, "ref_abs_max": m,
               "tolerance": 2.0 ** -6 * max(1.0, m)}
        for name, (e, m) in errs.items()}

    cfg = getattr(models, size["model"])(**dict(size["model_kwargs"],
                                                max_seq_len=t))
    opt = optax.adamw(3e-4, weight_decay=0.1)
    state = models.init_train_state(jax.random.PRNGKey(SEED), cfg, opt)
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                (size["kernel_batch"], t + 1), 0,
                                cfg.vocab_size)
    step, out["step_custom_calls"], out["step_compile_s"] = timed_compile(
        models.make_train_step(cfg, opt), state, {"tokens": tokens})
    t0 = time.perf_counter()
    _, metrics = step(state, {"tokens": tokens})
    out["step_loss"] = float(metrics["loss"])
    out["step_run_s"] = round(time.perf_counter() - t0, 4)
    out["step_vocab_size"] = cfg.vocab_size
    return out


def kernel_phase(size: dict, platform: str) -> dict:
    import ray_tpu

    ref = ray_tpu.remote(num_tpus=1)(kernel_body).remote(size)
    try:
        r = ray_tpu.get(ref, timeout=size["kernel_timeout_s"])
    except ray_tpu.exceptions.GetTimeoutError:
        raise TimeoutError(
            f"kernel phase did not finish in {size['kernel_timeout_s']}s "
            f"(the old 600-s Pallas hang?)") from None
    show("kernel", r)
    require(r["platform"] == platform,
            f"kernel worker ran on {r['platform']!r}, expected {platform!r}")
    for name, e in r["errors"].items():
        require(e["max_abs_err"] <= e["tolerance"],
                f"attention {name}: max |err| {e['max_abs_err']} over "
                f"tolerance {e['tolerance']} (ref max {e['ref_abs_max']})")
    require(math.isfinite(r["step_loss"]), f"T={r['shape'][1]} step loss "
            f"{r['step_loss']}")
    if platform == "tpu":
        require(r["device_count"] == 1,
                f"one-chip worker saw {r['device_count']} devices")
        # Forward is one kernel; the gradient runs it and the one
        # backward kernel (dq, dk and dv together since PR 38).
        require(r["fwd_custom_calls"] >= 1 and r["bwd_custom_calls"] >= 2
                and r["step_custom_calls"] >= 1,
                f"lowered text lacks tpu_custom_call (the kernel was "
                f"interpreted or gave way): {r}")
    return r


# ---------------------------------------------------------------------------
# the gated delta rule (ray_tpu/ops/linear_attention.py)


def delta_rule_body(size: dict) -> dict:
    """Runs in a one-chip worker: the chunked gated delta rule at a KDA
    layer's shape, forward and gradient timed, as ``gated_delta_rule``
    chooses its implementation there (``implementation``: the Pallas
    kernels on one TPU chip at 128-wide heads) and as the XLA scan
    (``scan_*``); then, on a prefix of the
    row, forward and all five gradients of both against the token-by-token
    recurrence (``chipbench/reference/kimi_linear.py`` ``delta_rule``,
    float32, ``highest``). Decays as the model's init gives them: up to
    1.6 a token and channel, 100 over a chunk."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference.kimi_linear import delta_rule
    from ray_tpu.ops import linear_attention as la

    dev = jax.devices()[0]
    b, t, h, d = size["delta_shape"]
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "shape": [b, t, h, d], "prefix": size["delta_prefix"],
           "chunk": la.CHUNK}
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    normal = lambda key: jax.random.normal(key, (b, t, h, d), jnp.float32)
    q, k = (la.l2_norm(normal(key)).astype(jnp.bfloat16) for key in ks[:2])
    v, w = (normal(key).astype(jnp.bfloat16) for key in ks[2:4])
    # log-uniform steps in [0.001, 0.1] x a head's rate in [1, 16]
    g = -jnp.exp(jax.random.uniform(ks[4], (b, t, h, d), jnp.float32,
                                    math.log(1e-3), math.log(1.6)))
    beta = jax.random.uniform(ks[5], (b, t, h), jnp.float32)
    operands = (q, k, v, g, beta)

    def loss(fn, weight):
        return lambda *a: (fn(*a).astype(jnp.float32)
                           * weight.astype(jnp.float32)).sum()

    def timed(fn, *args):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = round(time.perf_counter() - t0, 2)
        jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        result = jax.block_until_ready(compiled(*args))
        return result, compile_s, round(time.perf_counter() - t0, 5)

    # the op as ``gated_delta_rule`` chooses it here, and beside it the
    # XLA scan at the same shape (the same thing where the choice is the scan)
    out["implementation"] = ("kernels" if la._takes_kernels(q, v) else "scan")
    gradient = lambda fn: jax.grad(loss(fn, w), argnums=(0, 1, 2, 3, 4))
    o, out["fwd_compile_s"], out["fwd_run_s"] = timed(
        la.gated_delta_rule, *operands)
    out["finite"] = bool(jnp.isfinite(o.astype(jnp.float32)).all())
    _, out["grad_compile_s"], out["grad_run_s"] = timed(
        gradient(la.gated_delta_rule), *operands)
    _, _, out["scan_fwd_run_s"] = timed(la._by_scan, *operands)
    _, _, out["scan_grad_run_s"] = timed(gradient(la._by_scan), *operands)
    out["log_decay_min"] = float(la.log_decay_min(g))

    # A prefix is a whole problem: the rule is causal and starts at zero.
    n = size["delta_prefix"]
    short = tuple(a[:, :n] for a in operands)
    exact = tuple(a.astype(jnp.float32) for a in short)
    grad = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: (loss(fn, w[:, :n])(*a), fn(*a)), argnums=(0, 1, 2, 3, 4),
        has_aux=True))
    with jax.default_matmul_precision("highest"):
        (_, o_r), grads_r = grad(delta_rule)(*exact)

    def err(a, r):
        a, r = a.astype(jnp.float32), r.astype(jnp.float32)
        return {"max_abs_err": float(jnp.abs(a - r).max()),
                "mean_abs_err": float(jnp.abs(a - r).mean()),
                "ref_abs_max": float(jnp.abs(r).max()),
                "ref_abs_mean": float(jnp.abs(r).mean())}

    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    for key, fn in (("errors", la.gated_delta_rule),
                    ("scan_errors", la._by_scan)):
        (_, o_p), grads_p = grad(fn)(*short)
        out[key] = {name: err(a, r) for name, a, r in zip(
            names, (o_p, *grads_p), (o_r, *grads_r))}
    return out


# bfloat16 operands through the chunk's products (the intra-chunk
# matrices, the inverse's products, the state's) against float32: the
# mean error is held to this share of the reference's mean magnitude.
DELTA_RULE_TOLERANCE = 0.03


def delta_rule_phase(size: dict, platform: str) -> dict:
    import ray_tpu

    r = ray_tpu.get(ray_tpu.remote(num_tpus=1)(delta_rule_body).remote(size),
                    timeout=size["kernel_timeout_s"])
    show("delta_rule", r)
    require(r["platform"] == platform,
            f"delta-rule worker ran on {r['platform']!r}, expected "
            f"{platform!r}")
    require(r["finite"], "the delta rule's output has a non-finite value")
    if platform == "tpu":       # [1, 16384, 32, 128] on one chip: the kernels'
        require(r["implementation"] == "kernels",
                f"the delta rule ran as {r['implementation']!r} on the chip")
    for key in ("errors", "scan_errors"):
        for name, e in r[key].items():
            require(
                e["mean_abs_err"] <= DELTA_RULE_TOLERANCE * e["ref_abs_mean"],
                f"delta rule {key} {name}: mean |err| {e['mean_abs_err']} "
                f"over {DELTA_RULE_TOLERANCE} x the recurrence's mean "
                f"{e['ref_abs_mean']}")
    return r


# ---------------------------------------------------------------------------
# the afmoe arch's step (Trinity-Mini's block)


def afmoe_step_body(size: dict) -> dict:
    """Runs in a one-chip worker: the program's logits on one seeded row
    against the plain reference's (``chipbench/reference/afmoe.py``,
    float32, ``highest``) as the benchmark compares them (mean |d| over
    the reference's std), then one ``make_train_step`` on that row."""
    import jax
    import jax.numpy as jnp
    import optax

    from chipbench.reference import afmoe as reference
    from ray_tpu import models

    dev = jax.devices()[0]
    t = size["afmoe_seq"]
    cfg = models.trinity_mini_26b_a3b(**dict(size["afmoe"], max_seq_len=t))
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "seq": t, "n_params": cfg.num_params(),
           "kinds": [list(cfg.layer_kind(i)) for i in range(cfg.n_layers)]}
    opt = optax.adamw(1e-5, weight_decay=0.1)
    state = models.init_train_state(jax.random.PRNGKey(SEED), cfg, opt)
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (1, t + 1), 0,
                                cfg.vocab_size)
    z_p = jax.jit(lambda p, x: models.forward(p, x, cfg))(
        state["params"], tokens[:, :-1]).astype(jnp.float32)
    z_r = reference.forward(state["params"], tokens[:, :-1], cfg)
    out["logit_rel_d"] = float(jnp.abs(z_p - z_r).mean() / jnp.std(z_r))
    del z_p, z_r
    lowered = jax.jit(models.make_train_step(cfg, opt)).lower(
        state, {"tokens": tokens})
    out["step_custom_calls"] = lowered.as_text().count("tpu_custom_call")
    t0 = time.perf_counter()
    step = lowered.compile()
    out["step_compile_s"] = round(time.perf_counter() - t0, 2)
    t0 = time.perf_counter()
    _, metrics = step(state, {"tokens": tokens})
    out["step_loss"] = float(metrics["loss"])
    out["step_run_s"] = round(time.perf_counter() - t0, 4)
    out["attn_gate_mean"] = float(metrics["attn_gate_mean"])
    out["moe_held_share"] = float(metrics["moe_held_share"])
    out["first_loss_expected"] = (math.log(cfg.vocab_size)
                                  + 0.5 * cfg.d_model * 0.02 ** 2)
    return out


# the benchmark's limit on the same statistic (reference/_common.py)
AFMOE_LOGIT_REL_D = 0.03


def afmoe_step_phase(size: dict, platform: str) -> dict:
    import ray_tpu

    r = ray_tpu.get(ray_tpu.remote(num_tpus=1)(afmoe_step_body).remote(size),
                    timeout=size["kernel_timeout_s"])
    show("afmoe_step", r)
    require(r["platform"] == platform,
            f"afmoe worker ran on {r['platform']!r}, expected {platform!r}")
    require(r["kinds"] == [[True, True], [True, True], [False, False]],
            f"published layers 1 to 3 are S S F, the model ran {r['kinds']}")
    require(r["logit_rel_d"] <= AFMOE_LOGIT_REL_D,
            f"afmoe logits: mean |d| {r['logit_rel_d']} x the reference's "
            f"std, over {AFMOE_LOGIT_REL_D}")
    require(abs(r["step_loss"] - r["first_loss_expected"]) < 0.1,
            f"afmoe step loss {r['step_loss']}, the init predicts "
            f"{r['first_loss_expected']}")
    require(abs(r["attn_gate_mean"] - 0.5) < 0.02,
            f"attn_gate_mean {r['attn_gate_mean']} at a seeded init")
    if platform == "tpu":
        require(r["step_custom_calls"] >= 1,
                f"lowered text lacks tpu_custom_call: {r}")
    return r


# ---------------------------------------------------------------------------
# serve


def _post(url: str, payload: dict, timeout_s: float) -> tuple[int, dict]:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json",
                 # The first request compiles prefill and decode.
                 "X-Request-Timeout-S": str(timeout_s)})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s + 10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode("utf8", "replace")[:2000]}


def serve_phase(size: dict, chips: int, platform: str) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, build_openai_app

    config = LLMConfig(model_id=size["serve_model"], model=size["serve_model"],
                       max_num_seqs=size["serve_slots"],
                       max_seq_len=size["serve_seq"], seed=SEED)
    out: dict = {}
    t0 = time.time()
    app = build_openai_app(config, num_replicas=chips)
    serve.run(app, route_prefix="/v1")
    name = app.deployment.name
    base = f"http://127.0.0.1:{serve.get_proxy_port()}/v1"

    # Every replica up: its snapshot comes from the process that holds
    # the engine's arrays.
    controller = ray_tpu.get_actor("SERVE_CONTROLLER", namespace="serve")
    deadline = time.time() + size["request_timeout_s"]
    while True:
        replicas = ray_tpu.get(controller.get_replicas.remote(name))["replicas"]
        if len(replicas) == chips:
            break
        require(time.time() < deadline,
                f"{len(replicas)} of {chips} replicas after "
                f"{size['request_timeout_s']}s")
        time.sleep(0.25)

    def snapshots() -> dict:
        metrics = ray_tpu.get([a.get_metrics.remote() for _, a in replicas],
                              timeout=size["request_timeout_s"])
        return {rid: m for (rid, _), m in zip(replicas, metrics)}

    before = snapshots()
    out["replicas_up_s"] = round(time.time() - t0, 2)

    prompts = [(f"request {i}: " + "the quick brown fox jumps over the "
                "lazy dog. " * 8)[:size["prompt_tokens"] - 1]
               for i in range(size["n_requests"])]

    def burst() -> tuple[float, list]:
        t0 = time.time()
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            got = list(pool.map(lambda p: _post(
                f"{base}/completions",
                {"prompt": p, "max_tokens": size["max_tokens"],
                 "temperature": 0.0, "ignore_eos": True},
                size["request_timeout_s"]), prompts))
        return round(time.time() - t0, 2), got

    # The first burst pays the compiles; the second is what a warm
    # server answers in. Same prompts, greedy: the text must repeat.
    texts = []
    for label in ("first_burst_s", "second_burst_s"):
        out[label], got = burst()
        for status, body in got:
            require(status == 200, f"/v1/completions -> {status}: {body}")
            require(body["usage"]["completion_tokens"] == size["max_tokens"],
                    f"completion_tokens {body['usage']} != "
                    f"{size['max_tokens']}")
            require(body["usage"]["prompt_tokens"] >= size["prompt_tokens"] - 2,
                    f"prompt_tokens {body['usage']}")
        texts.append([body["choices"][0]["text"] for _, body in got])
    require(texts[0] == texts[1],
            "greedy completions differ between two identical bursts")

    status, chat = _post(
        f"{base}/chat/completions",
        {"messages": [{"role": "user", "content": "say something"}],
         "max_tokens": size["max_tokens"], "ignore_eos": True},
        size["request_timeout_s"])
    require(status == 200 and chat["object"] == "chat.completion"
            and chat["usage"]["completion_tokens"] == size["max_tokens"],
            f"/v1/chat/completions -> {status}: {chat}")

    after = snapshots()
    out["replicas"] = {
        rid: {**{k: m["engine"][k] for k in
                 ("platform", "device_kind", "n_devices", "chips")},
              "served": m["total"] - before[rid]["total"]}
        for rid, m in after.items()}
    show("serve", out)
    for rid, r in out["replicas"].items():
        require(r["platform"] == platform,
                f"replica {rid} serves from {r['platform']!r}, "
                f"expected {platform!r}")
        require(r["n_devices"] == 1, f"replica {rid}: {r}")
    if chips > 1:
        held = [r["chips"] for r in out["replicas"].values()]
        require(len(set(held)) == chips and None not in held,
                f"replicas do not hold {chips} distinct chips: {held}")
        require(sum(r["served"] > 0 for r in out["replicas"].values()) > 1,
                f"one replica served everything: {out['replicas']}")
    serve.shutdown()
    return out


# ---------------------------------------------------------------------------
# the run


def wait_chips_free(chips: int, timeout_s: float = 90.0) -> float:
    """Bounded wait until every chip is back in the pool — they come
    back when their holder's process has exited."""
    import ray_tpu

    t0 = time.time()
    while ray_tpu.available_resources().get("TPU", 0) < chips:
        require(time.time() - t0 < timeout_s,
                f"chips still held after {timeout_s}s: "
                f"{ray_tpu.available_resources()}")
        time.sleep(0.25)
    return round(time.time() - t0, 2)


def live_descendants() -> list[int]:
    """Pids of this process's live (non-zombie) descendants."""
    parent_of, state = {}, {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        state[int(pid)], parent_of[int(pid)] = fields[0], int(fields[1])
    out = []
    for pid in parent_of:
        p = pid
        while p in parent_of and p != os.getpid():
            p = parent_of[p]
        if p == os.getpid() and pid != os.getpid() and state[pid] != "Z":
            out.append(pid)
    return out


def native_lanes() -> dict:
    """Which native lanes this checkout built and loaded (they are built
    from src/ on first use; each has a pure-Python fallback)."""
    from ray_tpu._private import native_build

    native_build.ensure_native()
    out_dir = os.path.join(os.path.dirname(os.path.abspath(
        native_build.__file__)), "..", "_native")
    return {t: ("native" if os.path.exists(os.path.join(out_dir, t))
                else "python fallback")
            for t in ("libobjstore.so", "libsched.so", "libchannel.so",
                      "_specenc.so", "_evloop.so")}


def arm_watchdog(walls: dict) -> None:
    """A phase that hangs (only the kernel phase has a timeout of its
    own) must still end as a named failure inside the driver's limit,
    with every process this script started stopped."""

    def expire():
        print(f"chip_smoke: FAILED in phase {walls.get('running')!r}: "
              f"the run exceeded {BUDGET_S}s", file=sys.stderr, flush=True)
        for pid in live_descendants():
            with contextlib.suppress(OSError):
                os.kill(pid, signal.SIGKILL)
        os._exit(1)

    timer = threading.Timer(BUDGET_S, expire)
    timer.daemon = True
    timer.start()


def smoke(size: dict, platform: str = "tpu", *, watchdog: bool = False,
          **init_kwargs) -> dict:
    """Run every phase; returns the summary or raises PhaseFailed."""
    import tempfile

    import ray_tpu
    from ray_tpu._private import compile_cache

    t_start = time.time()
    walls: dict = {}
    if watchdog:
        arm_watchdog(walls)
    summary = {"size": size["name"], "walls_s": walls,
               "compile_cache": {
                   "dir": compile_cache.compile_cache_dir(),
                   "entries_before": compile_cache.compile_cache_entries()}}
    print(f"[chip_smoke] compile cache {summary['compile_cache']}",
          flush=True)
    try:
        with phase("detect", walls):
            # No num_tpus override from main(): detection is under test.
            ray_tpu.init(**init_kwargs)
            chips = int(ray_tpu.cluster_resources().get("TPU", 0))
            require(chips >= 1,
                    "no TPU chip detected on this machine "
                    f"(cluster resources: {ray_tpu.cluster_resources()}); "
                    "chip_smoke.py has no CPU mode")
            summary["chips"] = chips
            summary["native_lanes"] = native_lanes()
        with phase("train", walls):
            summary["train"] = train_phase(
                size, chips, platform,
                tempfile.mkdtemp(prefix="chip_smoke_run_"))
        with phase("kernel", walls):
            summary["chips_free_wait_s"] = [wait_chips_free(chips)]
            summary["kernel"] = kernel_phase(size, platform)
        with phase("delta_rule", walls):
            summary["chips_free_wait_s"].append(wait_chips_free(chips))
            summary["delta_rule"] = delta_rule_phase(size, platform)
        with phase("afmoe_step", walls):
            summary["chips_free_wait_s"].append(wait_chips_free(chips))
            summary["afmoe_step"] = afmoe_step_phase(size, platform)
        with phase("serve", walls):
            summary["chips_free_wait_s"].append(wait_chips_free(chips))
            summary["serve"] = serve_phase(size, chips, platform)
        with phase("shutdown", walls):
            ray_tpu.shutdown()
            deadline = time.time() + 30
            while (left := live_descendants()) and time.time() < deadline:
                time.sleep(0.25)
            require(not left, f"worker processes left behind: {left}")
    finally:
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
    summary["compile_cache"]["entries_after"] = \
        compile_cache.compile_cache_entries()
    walls["total"] = round(time.time() - t_start, 2)
    return summary


def report(run) -> int:
    """Exit code for one run; prints the result only when it passed."""
    try:
        summary = run()
    except PhaseFailed as e:
        traceback.print_exception(e.__cause__)
        print(f"chip_smoke: FAILED in phase {e.phase!r}: {e.__cause__!r}",
              file=sys.stderr, flush=True)
        return 1
    t = summary["train"]
    print(json.dumps(summary, indent=1))
    print(json.dumps({"ok": True, "device": {
        "platform": t["platform"], "kind": t["device_kind"],
        "count": t["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(report(lambda: smoke(FULL, watchdog=True)))
