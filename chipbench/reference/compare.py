"""Logits of the program against the plain reference at a configuration's
own widths, outside any timed window:

    python -m chipbench.reference.compare --config <config> --seed <n>

One seeded row (``traffic_gen.token_rows``, the cell's rows) of
``--seq-len`` tokens, weights from ``--seed`` as the train cells make
them; the program's ``models.forward`` in its compute dtype against
``reference/<arch>.py``'s float32 ``forward``. Prints max, mean and high
percentiles of |d| over all logits, per-token maxima, and one JSON line.
``--break key=value`` (a ``TransformerConfig`` field) runs the program
with one thing wrong, to show what a fault looks like beside rounding.

This process takes the chip itself (one device; for a configuration
whose float32 weights fit one chip). It measures no speed.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="python -m chipbench.reference.compare")
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--break", dest="broken", action="append", default=[])
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import spec, traffic_gen
    from ray_tpu import models

    data = spec.load_json("chipbench", "configs", args.config + ".json")
    cfg = spec.model_config(data)
    seq_len = args.seq_len or cfg.max_seq_len
    overrides = {}
    for item in args.broken:
        key, _, value = item.partition("=")
        overrides[key] = json.loads(value)
    run_cfg = spec.model_config(data, **overrides)

    params = jax.jit(lambda k: models.init_params(k, cfg))(
        jax.random.PRNGKey(args.seed))
    tokens = jnp.asarray(traffic_gen.token_rows(
        [0], args.seed, seq_len, cfg.vocab_size)[:, :-1])
    ref = spec.load_part("reference", data["arch"])
    want = np.asarray(ref.forward(params, tokens, cfg))
    got = np.asarray(jax.jit(
        lambda p, t: models.forward(p, t, run_cfg))(params, tokens))
    d = np.abs(got.astype(np.float64) - want)
    per_token = d.max(axis=-1).reshape(-1)
    out = {
        "config": args.config, "seed": args.seed, "seq_len": seq_len,
        "broken": overrides, "device": jax.devices()[0].device_kind,
        "logit_std": float(want.std()),
        "max_abs_d": float(d.max()), "mean_abs_d": float(d.mean()),
        "p99_abs_d": float(np.quantile(d.reshape(-1)[::97], 0.99)),
        "token_max_median": float(np.median(per_token)),
        "token_max_p99": float(np.quantile(per_token, 0.99)),
        "tokens_over_10x_median": int((per_token
                                       > 10 * np.median(per_token)).sum()),
        "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean()),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
