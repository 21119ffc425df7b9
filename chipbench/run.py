"""One run of one cell:

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``. Earlier lines say what was counted, give each number
compared beside its limit (``compared ...``) and name the checks behind
``correct``; those two kinds are the last lines of stderr too.

There is no CPU mode: on a machine with no chip, or fewer chips than the
cell asks for, the command prints no result and exits non-zero. This
process never initialises a jax backend; the runtime's chip-holding
workers do.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             root: str | None = None, rehearsal: dict | None = None) -> dict:
    """Run one cell and return the result object (the Python API; the
    CLI prints it). ``rehearsal`` is for the tests only: ``ray_tpu.init``
    keyword arguments with a fake ``num_tpus``, which makes the run a CPU
    rehearsal whose result says ``platform: cpu``."""
    from chipbench import spec, stats, xplane

    root = os.path.abspath(root or spec.ROOT)
    # Workers import chipbench (the train loop and its reference): put
    # this checkout on their path.
    paths = [root, spec.ROOT] + [p for p in os.environ.get(
        "PYTHONPATH", "").split(os.pathsep) if p]
    os.environ["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(paths))
    cell = spec.load_cell(workload, root)
    notes: list[str] = []
    ctx = {"cell": cell, "seed": int(seed), "seconds": float(seconds),
           "trace": bool(trace), "root": root, "notes": notes,
           "t_start": T_START, "on_chip": rehearsal is None,
           "init_kwargs": dict(rehearsal or {})}
    driver = spec.load_part("drivers", cell["traffic_data"]["kind"])
    run = driver.run(ctx)
    run.update(cell=cell, seconds=ctx["seconds"], notes=notes, root=root)

    device = dict(run["device"])
    want = "tpu" if rehearsal is None else "cpu"
    if device["platform"] != want or (
            rehearsal is None and device["count"] != cell["chips"]):
        raise RuntimeError(f"the cell's workers ran on {device}, expected "
                           f"{want} with {cell['chips']} device(s)")
    run["peaks"] = (spec.load_peaks(device["kind"], root)
                    if rehearsal is None else None)
    run["trace"] = None
    if trace and run.get("trace_dir"):
        path = xplane.find_xplane(run["trace_dir"])
        if path:
            run["trace"] = xplane.load(path)

    section = "per_layer" if trace else "end_to_end"
    directory = "layer_metrics" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_of(workload, section, root):
        value = spec.load_part(directory, m["name"]).read(run)
        if value is None:
            notes.append(f"metric {m['name']}: nothing to read, left out")
            continue
        shown = stats.finite(float(value))
        if shown != value:
            notes.append(f"metric {m['name']} is {value}; printed as {shown}")
        metrics[m["name"]] = {"value": shown, "unit": m["unit"]}
    for name, ok in run["checks"].items():
        notes.append(f"check {name}: {'ok' if ok else 'FAILED'}")
    result = {"correct": all(run["checks"].values()),
              "attempted": run["attempted"], "failed": run["failed"],
              "metrics": metrics, "device": device}
    if trace:
        t = run["trace"]
        device["busy_s"] = xplane.busy_s(t) if t else 0.0
        device["window_s"] = (run.get("traced") or {}).get("window_s", 0.0)
        if t:
            result["breakdown"] = {"device_ops": xplane.top_device_ops(t),
                                   "idle_gaps": xplane.idle_gaps(t)}
            spans = ", ".join(f"{name} {secs:.3f} s"
                              for name, secs in xplane.container_ops(t, 4))
            notes.append("trace: control-flow instructions, not counted as "
                         f"busy (their bodies are): {spans or 'none'}")
            spans = ", ".join(f"{name} {secs:.4f} s" for name, secs
                              in xplane.exposed_collectives(t, 6))
            notes.append(f"trace: collectives most exposed: {spans or 'none'}")
    result["notes"] = notes
    _keep_detail(run, result, root, trace)
    return result


def _keep_detail(run: dict, result: dict, root: str, trace: bool) -> None:
    """The run's raw record (the train report) beside the caches, for
    whoever has to explain a number; the next run of the cell overwrites
    it."""
    from chipbench import spec

    detail = {"result": result, "train": run.get("train")}
    path = os.path.join(spec.cache_dir(root),
                        f"last-run-{run['cell']['name']}-t{int(trace)}.json")
    with open(path, "w") as f:
        json.dump(detail, f, default=str)


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    p = argparse.ArgumentParser(prog="python -m chipbench.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seconds is None:
        from chipbench import spec

        args.seconds = float(spec.load_benchmark()["run_seconds"])
    result = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace))
    notes = result.pop("notes")
    for note in notes:
        print(f"[chipbench] {note}", flush=True)
    print(json.dumps(result), flush=True)
    # Each number compared beside its limit, and the checks, as the last
    # lines of standard error too: what is kept of a run that is not correct.
    for note in notes:
        if note.startswith(("compared ", "check ")):
            print(f"[chipbench] {note}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
