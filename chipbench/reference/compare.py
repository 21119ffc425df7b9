"""The program against its plain reference at a configuration's own
widths, outside any timed window, sound or with one thing wrong:

    python -m chipbench.reference.compare --config <config> --seed <n>
        [--control <name>[=<value>]] [--break key=value] [--set key=value]

It runs the comparison that decides a train cell's ``correct``
(``_common.agreement``: the SAME function ``drivers/train_job.py`` calls,
on the program's side through the driver's own jitted call), on
``--rows`` seeded rows (``traffic_gen.token_rows``, the cell's first
rows) of ``--seq-len`` tokens, weights from ``--seed`` as the train cells
make them. One JSON line a comparison: the statistics, their limits, and
which are outside.

``--control`` gives ONE side weights with one thing wrong
(``_common.CONTROLS``): on the program's side ``drop_layer=<i>``,
``drop_experts=<i>``, ``scale_layers=<f>``, ``float8_weights``; on the
reference's side ``bf16_weights`` (not a fault: how much of a reading is
rounding of the weights). ``--break key=value`` (a ``TransformerConfig``
field) runs the PROGRAM under another configuration than the reference;
``--set key=value`` changes it for both (a depth that fits one chip).
``--seed`` and ``--control`` repeat: every control on every seed in one
process (``sound`` is the name of no control).

This process takes the chip itself (one device; for a configuration
whose float32 weights fit one chip twice over). It measures no speed.
"""

from __future__ import annotations

import argparse
import json
import sys


def _pairs(items: list[str]) -> dict:
    out = {}
    for item in items:
        key, _, value = item.partition("=")
        out[key] = json.loads(value)
    return out


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="python -m chipbench.reference.compare")
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, action="append", default=[])
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--rows", type=int, default=1)
    p.add_argument("--control", action="append", default=[])
    p.add_argument("--break", dest="broken", action="append", default=[])
    p.add_argument("--set", dest="both", action="append", default=[])
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import spec, traffic_gen
    from chipbench.drivers import train_job
    from chipbench.reference import _common
    from ray_tpu import models

    data = spec.load_json("chipbench", "configs", args.config + ".json")
    both, broken = _pairs(args.both), _pairs(args.broken)
    cfg = spec.model_config(data, **both)
    seq_len = args.seq_len or cfg.max_seq_len
    call = train_job.program_side(spec.model_config(data, **both, **broken))
    ref = spec.load_part("reference", data["arch"])
    limits = _common.limits_for(data.get("agreement_limits"))
    for seed in args.seed or [0]:
        params = jax.jit(lambda k: models.init_params(k, cfg))(
            jax.random.PRNGKey(seed))
        rows = traffic_gen.token_rows(range(args.rows), seed, seq_len,
                                      cfg.vocab_size)
        for control in args.control or ["sound"]:
            for_program, for_reference = _common.apply_control(
                None if control == "sound" else control, params)

            def program():
                tokens = jnp.asarray(rows)
                return call(for_program, tokens, tokens)[:2]

            stats = _common.agreement(ref, for_reference, rows, cfg, program)
            bad = _common.outside(stats, limits)
            print(json.dumps({
                "config": args.config, "seed": seed, "seq_len": seq_len,
                "rows": args.rows, "control": control, "set": both,
                "broken": broken, "device": jax.devices()[0].device_kind,
                **stats, "limits": limits, "outside": bad}),
                flush=True)
            del for_program, for_reference
    return 0


if __name__ == "__main__":
    sys.exit(main())
