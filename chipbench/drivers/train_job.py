"""Training through ``JaxTrainer.fit()``: one (mesh) worker holding the
cell's chips, ``ray_tpu.data`` seeded token rows -> ``iter_jax_batches``,
the model's default step options, AdamW, a host fetch of the loss every
``fetch_every`` steps, no save in the window. The loop below is the
benchmark's own copy of ``chip_smoke.train_loop``'s pattern; all clocks
are in the worker that holds the chips.

What decides ``correct`` (the checks at the end of ``run``): before the
optimizer's state exists, ONE jitted call (``program_side``) gives the
program's logits on the first rows of the first batch, which
``reference/_common.py`` ``agreement`` compares with the plain
reference's token by token, and the program's whole loss on the whole
first batch, which the jitted step's own first loss is held to; so the
step that is timed is tied to the forward that was compared. The window
fetches a group's last loss and nothing else; the last step's other
scalars are fetched once, after it (``step_metrics``).
"""

from __future__ import annotations

import functools
import json
import math
import os
import time

from chipbench import spec, traffic_gen, xplane

# Same seed, same rows, same cached program on the same kind of chip: the
# losses repeat exactly on the v5e (PERF.md section 6). 1e-3 leaves room
# for a recompile that reorders a reduction in bfloat16, and is far under
# the step-to-step change of the loss (> 1e-2).
LOSS_REPEAT_TOLERANCE = 1e-3
# The step's own first loss (the jitted ``train_step``'s report for step
# 0, before any update) against the program's whole loss on the whole
# first batch, taken in the set-up call whose logits were compared with
# the reference: the same arithmetic fused two ways (one program has the
# backward in it). Sound, on the v5e: |d| <= 1.9e-6 in the dense cells (26
# runs), <= 5.2e-5 in OLMoE's (10), <= 7.6e-5 in SmallThinker's (12). The
# fault it is there for, the step not being the forward that was compared
# (the whole cell with the compared weights wrong): a layer apart 0.0019
# (Mistral, the last of 10) 0.0109 (OLMoE, its one) 0.0112 (SmallThinker,
# the last of 4); a router term absent from the step is the term, 0.0100-
# 0.0165. 2.5e-4 is 3.3 x the sound runs' largest and 7.6 x under the
# smallest of those. It is a difference of two MEAN losses, so at
# SmallThinker's 16,384 positions float8 weights read 2.3e-4 and the last
# layer's held experts out 7.6e-5, inside it, where the per-token
# comparison reads them 9 x and 6 x the sound program: this TIES the step
# to the compared forward, it does not compare the step (PERF.md sections
# 4 and 7).
FIRST_STEP_TOLERANCE = 2.5e-4
# AdamW's first update of a weight is -lr x (sign of its gradient + the
# weight decay x the weight), and the next few are at most as large: the
# mean |change| of the smallest leaf (a norm's weights or a bias) over the
# warm-up steps, as a multiple of lr a step. On the v5e, two steps: 1.27-
# 1.30 (GPT-2 XL: a bias), 2.05-2.08 (OLMoE), 1.93-1.97 (SmallThinker),
# 2.15 (Mistral) x lr (PERF.md section 6, PR 34). A step that returns its
# state unchanged reads 0, the one fault this is there for: a floor and no
# ceiling (an update applied twice would read 2.5-4.3, which no one bound
# parts from 2.15), on ONE leaf: an update that is wrong or missing in
# another leaf passes (PERF.md section 7; gradients are held on the CPU).
WEIGHTS_MOVED_FLOOR = 0.25
# The program draws every weight from N(0, 0.02), so the first logits are
# near-Gaussian with variance d_model * 0.02^2 (unit-variance normed
# hidden state against the head's columns) and the first loss is
# ln(vocab) + variance / 2: 11.15 for GPT-2 XL, 11.19 at Mistral-7B's
# widths (measured 11.14 and 11.17-11.19). 0.1 covers the sampling noise
# of a batch and the non-Gaussian tail; a loss near ln(vocab) itself
# would mean the model's output is not reaching the loss.
FIRST_LOSS_TOLERANCE = 0.1
INIT_STD = 0.02
TRACE_SECONDS = 5.0


def make_rows(batch: dict, *, seed: int, seq_len: int, vocab: int) -> dict:
    """map_batches UDF: row ids -> seeded token rows [n, seq_len + 1]."""
    return {"tokens": traffic_gen.token_rows(batch["id"], seed, seq_len,
                                             vocab)}


def moment_shardings(opt, shapes, shardings, replicated):
    """Shardings for ``opt.init``'s state: a leaf shaped like a parameter
    takes that parameter's sharding, anything else is replicated."""
    import jax

    by_shape = {}
    for s, sh in zip(jax.tree.leaves(shapes), jax.tree.leaves(shardings)):
        by_shape.setdefault(s.shape, sh)
    return jax.tree.map(
        lambda s: by_shape.get(s.shape, replicated) if s.ndim else replicated,
        jax.eval_shape(opt.init, shapes))


def program_side(cfg, mesh=None, whole: bool = True):
    """The program's side of the comparison with the reference, one jitted
    call: (params, the sample rows [k, T + 1], the whole first batch
    [rows, T + 1]) -> (float32 logits of the sample's first T positions,
    the whole training loss on the sample, the whole training loss on the
    batch as the step takes it). ``whole``: the sample IS the batch."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import models

    @jax.jit
    def call(p, s, b):
        z = models.forward(p, s[:, :-1], cfg).astype(jnp.float32)
        on_sample = models.lm_loss(p, {"tokens": s}, cfg)[0]
        return z, on_sample, (on_sample if whole else models.lm_loss(
            p, {"tokens": b}, cfg, mesh=mesh)[0])

    return call


def train_loop(config: dict) -> None:
    """Runs in the chip-holding worker."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from chipbench.reference import _common
    from ray_tpu import models, train
    from ray_tpu._private import compile_cache
    from ray_tpu.parallel import (MeshConfig, batch_sharding,
                                  infer_param_specs, make_shardings)

    cfg_data, t = config["config_data"], config["traffic"]
    seed, seconds = config["seed"], config["seconds"]
    devices = jax.devices()
    mesh_devices = devices[:config["chips"]]
    n = len(mesh_devices)
    cfg = spec.model_config(cfg_data)
    mesh = MeshConfig(data=1, fsdp=-1).build(mesh_devices)
    o = cfg_data["optimizer"]
    opt = optax.adamw(o["learning_rate"], weight_decay=o["weight_decay"])
    replicated = NamedSharding(mesh, PartitionSpec())

    # Weights on the mesh in one jitted call from the seed, never on one
    # device first.
    shardings = make_shardings(mesh, infer_param_specs(
        cfg.shapes(), mesh, models.partition_specs(cfg)))
    params = jax.jit(lambda k: models.init_params(k, cfg),
                     out_shardings=shardings)(jax.random.PRNGKey(seed))

    # The program against the plain reference, token by token, on the
    # first rows of the first batch, and the program's whole loss on ALL
    # of that batch (what the step's first loss is held to): one jitted
    # call, before the optimizer state exists. The logits are deleted,
    # and the delete waited for, before anything that stays is placed.
    rows = t["rows_per_chip"] * n
    first = traffic_gen.token_rows(range(rows), seed, t["seq_len"],
                                   cfg.vocab_size)
    sample = first[:t["reference_rows"]]
    call = program_side(cfg, mesh, whole=len(sample) == rows)
    first_batch_loss = None

    def program():
        nonlocal first_batch_loss
        z, on_sample, first_batch_loss = call(
            params, jnp.asarray(sample),
            jax.device_put(first, batch_sharding(mesh)))
        return z, on_sample

    t_ref = time.perf_counter()
    agreement = _common.agreement(
        spec.load_part("reference", cfg_data["arch"]), params, sample, cfg,
        program)
    first_batch_loss = float(first_batch_loss)
    ref_s = time.perf_counter() - t_ref

    # The moments are sharded like the parameters they belong to and the
    # optimizer's scalars are replicated, all committed: left to itself
    # ``jax.jit(opt.init)`` replicates every moment on every chip (zeros
    # depend on no input), which four chips cannot hold, and optax's
    # uncommitted scalar count makes the second step compile again.
    state = {
        "params": params,
        "opt_state": jax.jit(opt.init, out_shardings=moment_shardings(
            opt, cfg.shapes(), shardings, replicated))(params),
        "step": jax.device_put(jnp.zeros((), jnp.int32), replicated),
    }
    state_shardings = jax.tree.map(lambda x: x.sharding, state)
    step = jax.jit(models.make_train_step(cfg, opt, mesh=mesh),
                   donate_argnums=(0,),
                   out_shardings=(state_shardings, None))

    batches = train.get_dataset_shard("train").iter_jax_batches(
        batch_size=rows, sharding=batch_sharding(mesh))
    # The smallest leaf of the weights, read to the host before the first
    # step and after the warm-up steps: how far the step moves a weight.
    leaves = jax.tree.leaves(state["params"])
    probe = min(range(len(leaves)), key=lambda i: leaves[i].size)
    probe_before = np.asarray(leaves[probe], np.float64)
    del leaves
    tokens_per_step = rows * t["seq_len"]
    losses: list[float] = []
    first_rows_match = None
    t_first = time.perf_counter()
    for i in range(t["warmup_steps"]):
        batch = next(batches)
        if i == 0:
            first_rows_match = bool(np.array_equal(
                np.asarray(batch["tokens"]), first))
        state, metrics = step(state, {"tokens": batch["tokens"]})
        losses.append(float(metrics["loss"]))
    weights_moved = float(np.abs(np.asarray(
        jax.tree.leaves(state["params"])[probe], np.float64)
        - probe_before).mean())
    warm_s = time.perf_counter() - t_first

    entries0 = compile_cache.compile_cache_entries()
    groups, group_s, input_s = [], [], 0.0
    traced = None
    trace_dir = config.get("trace_dir")
    trace_from = 0.4 * seconds if trace_dir else math.inf
    trace_t0 = None
    exhausted = False
    setup_s = time.time() - config["t_start"]
    t0 = time.perf_counter()
    while not exhausted:
        if trace_t0 is None and time.perf_counter() - t0 >= trace_from:
            xplane.start_trace(trace_dir)
            trace_t0, trace_from = time.perf_counter(), math.inf
        g0 = time.perf_counter()
        for _ in range(t["fetch_every"]):
            t_in = time.perf_counter()
            try:
                batch = next(batches)
            except StopIteration:
                exhausted = True
                break
            input_s += time.perf_counter() - t_in
            state, metrics = step(state, {"tokens": batch["tokens"]})
        if exhausted:
            break
        loss = float(metrics["loss"])      # host fetch: the group is done
        now = time.perf_counter()
        if trace_t0 is not None and traced is None and (
                now - trace_t0 >= min(TRACE_SECONDS, 0.4 * seconds)):
            jax.profiler.stop_trace()
            traced = {"window_s": now - trace_t0}
        if now - t0 > seconds:
            break                          # this group crossed the end
        losses.append(loss)
        group_s.append(now - g0)
        groups.append({"t_end": now - t0, "steps": t["fetch_every"],
                       "tokens": tokens_per_step * t["fetch_every"]})
    if trace_t0 is not None and traced is None:
        jax.profiler.stop_trace()
        traced = {"window_s": time.perf_counter() - trace_t0}
    entries1 = compile_cache.compile_cache_entries()
    # The LAST step's counters, fetched once, after the window has closed.
    step_metrics = {k: float(v) for k, v in jax.device_get(metrics).items()
                    if np.ndim(v) == 0}

    mem = [d.memory_stats() or {} for d in mesh_devices]
    train.report({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": dict(mesh.shape),
        "n_params": cfg.num_params(),
        "vocab_size": cfg.vocab_size,
        "d_model": cfg.d_model,
        "tokens_per_step": tokens_per_step,
        "setup_s": setup_s,
        "reference_s": ref_s, "warmup_s": warm_s,
        "agreement": agreement,
        "reference_loss": agreement["reference_loss"],
        "reference_ce": agreement["reference_ce"],
        "system_loss_on_sample": agreement["program_loss"],
        "first_batch_loss": first_batch_loss,
        "weights_moved": weights_moved,
        "step_metrics": step_metrics,
        "first_rows_match": first_rows_match,
        "losses": losses,
        "groups": groups, "group_s": group_s,
        "input_wait_s": input_s,
        "window_s": groups[-1]["t_end"] if groups else 0.0,
        "exhausted": exhausted,
        "compiles": step._cache_size(),
        "cache_entries_added_in_window": entries1 - entries0,
        "traced": traced,
        "param_devices_min": min(len(p.sharding.device_set)
                                 for p in jax.tree.leaves(state["params"])),
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
    })


def _losses_repeat(ctx: dict, losses: list[float], notes: list[str]) -> bool:
    """The first losses repeat those of this cell's first run in this
    checkout with the same seed (same weights, rows and order)."""
    n = ctx["cell"]["traffic_data"]["loss_repeat_count"]
    path = os.path.join(spec.cache_dir(ctx["root"]),
                        f"losses-{ctx['cell']['name']}-seed{ctx['seed']}.json")
    head = losses[:n]
    if not os.path.exists(path):
        with open(path, "w") as f:
            json.dump(head, f)
        notes.append(f"losses: first run with seed {ctx['seed']} here, "
                     f"{len(head)} kept")
        return True
    with open(path) as f:
        kept = json.load(f)
    m = min(len(kept), len(head))
    worst = max((abs(a - b) for a, b in zip(kept[:m], head[:m])), default=0.0)
    notes.append(f"losses: {m} compared with this seed's first run, max |d| "
                 f"{worst:.2e} (tolerance {LOSS_REPEAT_TOLERANCE})")
    return m > 0 and worst <= LOSS_REPEAT_TOLERANCE


def run(ctx: dict) -> dict:
    import ray_tpu
    import ray_tpu.data
    from chipbench.reference import _common
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cell, notes = ctx["cell"], ctx["notes"]
    cfg_data, t = cell["config_data"], cell["traffic_data"]
    chips = cell["chips"]
    vocab = spec.model_config(cfg_data).vocab_size
    trace_dir = (xplane.trace_dir(spec.cache_dir(ctx["root"]), cell["name"])
                 if ctx["trace"] else None)
    ray_tpu.init(**ctx["init_kwargs"])
    try:
        have = int(ray_tpu.cluster_resources().get("TPU", 0))
        if have < chips:
            raise RuntimeError(f"cell {cell['name']} needs {chips} chip(s); "
                               f"this machine has {have}")
        n_rows = (t["warmup_steps"] + t["max_steps"]) * t["rows_per_chip"] * chips
        ds = ray_tpu.data.range(n_rows).map_batches(functools.partial(
            make_rows, seed=ctx["seed"], seq_len=t["seq_len"], vocab=vocab))
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "config_data": cfg_data, "traffic": t, "chips": chips,
                "seed": ctx["seed"], "seconds": ctx["seconds"],
                "t_start": ctx["t_start"], "trace_dir": trace_dir},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True, tpus_per_worker=chips,
                topology="mesh"),
            datasets={"train": ds},
            run_config=RunConfig(
                name=f"chipbench-{cell['name']}",
                storage_path=os.path.join(spec.cache_dir(ctx["root"]),
                                          "train_runs")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    r = dict(result.metrics)
    losses = r["losses"]
    # What the reference's loss adds to the cross entropy on the sample
    # rows (router losses and the like; 0 where it exports no ``loss``)
    # is in the first step's loss too.
    ref_rest = r["reference_loss"] - r["reference_ce"]
    first_expected = (math.log(r["vocab_size"])
                      + 0.5 * r["d_model"] * INIT_STD ** 2 + ref_rest)
    notes.append(
        f"train: {len(r['groups'])} fetch groups of {t['fetch_every']} steps "
        f"in {r['window_s']:.2f} s; reference {r['reference_s']:.1f} s, "
        f"warm-up {r['warmup_s']:.1f} s; reference loss "
        f"{r['reference_loss']:.5f} = cross entropy {r['reference_ce']:.5f} "
        f"+ the rest {ref_rest:.5f}, program "
        f"{r['system_loss_on_sample']:.5f}, "
        f"first step {losses[0]:.5f}, expected {first_expected:.5f}")
    a = r["agreement"]
    limits = _common.limits_for(cfg_data.get("agreement_limits"))
    first_step_d = abs(r["first_batch_loss"] - losses[0])
    notes.append(
        f"program against reference on {a['positions']} positions: "
        + ", ".join(f"{k} {a[k]:.3e}" for k in _common.RECORDED)
        + f" (logit std {a['logit_std']:.4f}, largest |d| "
        f"{a['max_abs_d']:.3f}); whole first batch "
        f"{r['first_batch_loss']:.6f}, the step's first loss "
        f"{losses[0]:.6f}")
    notes.append("the last step's counters: " + ", ".join(
        f"{k} {v:.6g}" for k, v in sorted(r["step_metrics"].items())))
    notes.extend(_common.compared(a, limits))
    first_step_ok = first_step_d <= FIRST_STEP_TOLERANCE
    moved = r["weights_moved"] / cfg_data["optimizer"]["learning_rate"]
    floor = WEIGHTS_MOVED_FLOOR * t["warmup_steps"]
    moved_ok = moved >= floor
    for what, ok in (
            (f"first_step_d {first_step_d!r} (limit <= {FIRST_STEP_TOLERANCE})",
             first_step_ok),
            (f"weights_moved {moved!r} x the learning rate over "
             f"{t['warmup_steps']} warm-up step(s) (limit >= {floor})",
             moved_ok)):
        notes.append(f"compared {what}: {'ok' if ok else 'OUTSIDE'}")
    peak = max((b or 0) for b in r["peak_bytes_in_use"])
    checks = {
        "every_loss_finite": all(math.isfinite(x) for x in losses),
        "first_loss_as_the_init_predicts":
            abs(losses[0] - first_expected) < FIRST_LOSS_TOLERANCE,
        "program_agrees_with_reference": not _common.outside(a, limits),
        "first_step_is_the_compared_forward": first_step_ok,
        "step_moves_the_weights": moved_ok,
        "first_rows_are_the_seeded_rows": r["first_rows_match"] is True,
        "losses_repeat_first_run": _losses_repeat(ctx, losses, notes),
        "step_compiled_once": r["compiles"] == 1,
        "nothing_compiled_in_window":
            r["cache_entries_added_in_window"] == 0,
        "ran_whole_groups": len(r["groups"]) >= 1,
        "params_on_every_chip": r["param_devices_min"] == chips
            or not ctx["on_chip"],
    }
    n_steps = sum(g["steps"] for g in r["groups"])
    return {
        "kind": "train", "train": r, "checks": checks,
        "setup_s": r["setup_s"],
        "attempted": n_steps, "failed": 0,
        "traced": r["traced"], "trace_dir": trace_dir,
        "device": {"platform": r["platform"], "kind": r["device_kind"],
                   "count": r["device_count"], "memory_peak_bytes": peak},
    }
