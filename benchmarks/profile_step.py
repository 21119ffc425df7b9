"""Component-level timing of the headline GPT-2 train step (run on TPU).

Times the full step, forward/backward of the loss, forward/backward of
the body alone (no LM head / CE), and the optimizer, to locate where
the ~270ms step goes.  python benchmarks/profile_step.py
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from ray_tpu import models
from ray_tpu.models import transformer as T
from ray_tpu.ops.optim import FusedClipAdamW


def _sync(out):
    jax.block_until_ready(out)


def timeit(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / n


def main():
    batch, seq = 24, 1024
    cfg = models.gpt2_small(max_seq_len=seq, remat=False, scan_layers=False,
                            loss_chunk=4096)
    opt = FusedClipAdamW(learning_rate=3e-4, weight_decay=0.1, clip_norm=1.0)
    state = models.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg.vocab_size)
    b = {"tokens": tokens}

    step = jax.jit(models.make_train_step(cfg, opt))
    t_step = timeit(lambda s: step(s, b)[1], state)
    print(f"full step:            {t_step*1e3:8.2f} ms   "
          f"({batch*seq/t_step:,.0f} tok/s)", flush=True)

    fwd = jax.jit(lambda p, bb: T.lm_loss(p, bb, cfg)[0])
    t_fwd = timeit(fwd, state["params"], b)
    print(f"forward (loss):       {t_fwd*1e3:8.2f} ms", flush=True)

    grad = jax.jit(lambda p, bb: jax.grad(
        lambda pp: T.lm_loss(pp, bb, cfg)[0])(p))
    t_grad = timeit(grad, state["params"], b)
    print(f"fwd+bwd (grad):       {t_grad*1e3:8.2f} ms", flush=True)

    # body only: forward() returns hidden states (or logits?) — check
    body_in = tokens[:, :-1]
    bodyf = jax.jit(lambda p, t: jnp.sum(
        T.forward(p, t, cfg, return_hidden=True).astype(jnp.float32))
        if "return_hidden" in T.forward.__code__.co_varnames else None)
    try:
        t_body = timeit(bodyf, state["params"], body_in)
        print(f"fwd body (hidden):    {t_body*1e3:8.2f} ms", flush=True)
        gbody = jax.jit(lambda p, t: jax.grad(lambda pp: jnp.sum(
            T.forward(pp, t, cfg, return_hidden=True).astype(jnp.float32)))(p))
        t_gb = timeit(gbody, state["params"], body_in)
        print(f"fwd+bwd body:         {t_gb*1e3:8.2f} ms", flush=True)
    except Exception as e:
        print("body-only timing skipped:", type(e).__name__, str(e)[:120])

    grads = grad(state["params"], b)

    def opt_only(p, g, s):
        p2, s2, gnorm = opt.apply(g, s, p)
        return p2

    jopt = jax.jit(opt_only)
    t_opt = timeit(jopt, state["params"], grads, state["opt_state"])
    print(f"optimizer+apply:      {t_opt*1e3:8.2f} ms", flush=True)


if __name__ == "__main__":
    main()
