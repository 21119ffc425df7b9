"""ray-tpu CLI: start/status/submit/list/timeline.

Counterpart of the reference's CLI (python/ray/scripts/scripts.py —
`ray start` :647, `ray status`, `ray submit`, `ray timeline`, `ray list`
via util.state). `start --head` runs a standalone head service;
`start --address` joins as a node agent.

    ray-tpu start --head --port 6380 --num-cpus 8
    ray-tpu start --address 127.0.0.1:6380 --num-cpus 4
    ray-tpu status --address 127.0.0.1:6380
    ray-tpu submit --address 127.0.0.1:6380 -- python my_job.py
    ray-tpu list tasks --address 127.0.0.1:6380
    ray-tpu timeline --address 127.0.0.1:6380 -o trace.json
"""

from __future__ import annotations

import argparse
import json
import shlex
import sys


def _connect(address: str) -> None:
    import ray_tpu

    ray_tpu.init(address=address)


def cmd_start(args) -> int:
    if args.head:
        from ray_tpu._private.config import Config
        from ray_tpu._private.gcs import Head

        # RAY_TPU_<FIELD> applies here as it does in ray_tpu.init().
        cfg = Config().apply_overrides()
        cfg.head_host = args.host
        cfg.head_port = args.port
        if args.object_store_memory:
            cfg.object_store_memory = int(args.object_store_memory)
        if getattr(args, "snapshot_path", None):
            # Head FT: persist durable tables; a restart with the same
            # path restores them (reference: redis-backed GCS state).
            cfg.gcs_snapshot_path = args.snapshot_path
        if getattr(args, "external_store", None):
            # Cross-node head HA: durable state in a shared store; a
            # fresh head anywhere restores it (redis_store_client.h:111).
            cfg.gcs_external_store = args.external_store
        head = Head(
            cfg, num_cpus=args.num_cpus, num_tpus=args.num_tpus,
            resources=json.loads(args.resources) if args.resources else None)
        host, port = head.address
        if host == "0.0.0.0":
            import socket

            try:
                shown = socket.gethostbyname(socket.gethostname())
            except OSError:
                shown = "<this-host>"
        else:
            shown = host
        print(f"ray_tpu head up at {shown}:{port}", flush=True)
        print(f"  connect: ray_tpu.init(address='{shown}:{port}')", flush=True)
        print(f"  join:    ray-tpu start --address {shown}:{port}", flush=True)
        try:
            import threading

            threading.Event().wait()  # serve forever
        except KeyboardInterrupt:
            head.shutdown()
        return 0
    if not args.address:
        print("either --head or --address is required", file=sys.stderr)
        return 2
    from ray_tpu._private.node_agent import NodeAgent

    host, port = args.address.rsplit(":", 1)
    agent = NodeAgent(
        (host, int(port)),
        num_cpus=args.num_cpus,
        num_tpus=args.num_tpus,
        resources=json.loads(args.resources) if args.resources else None,
        node_id=args.node_id,
        force_remote_objects=args.force_remote_objects,
    )
    print(f"node agent up: node_id={agent.node_id}", flush=True)
    try:
        agent.run_forever()
    except KeyboardInterrupt:
        agent.shutdown()
    return 0


def cmd_status(args) -> int:
    import ray_tpu

    _connect(args.address)
    info = {
        "resources_total": ray_tpu.cluster_resources(),
        "resources_available": ray_tpu.available_resources(),
        "nodes": ray_tpu.nodes(),
    }
    print(json.dumps(info, indent=2, default=str))
    return 0


def cmd_submit(args) -> int:
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(address=args.address)
    entry = args.entrypoint
    if entry and entry[0] == "--":
        entry = entry[1:]
    entrypoint = shlex.join(entry)
    job_id = client.submit_job(entrypoint=entrypoint)
    print(f"submitted {job_id}")
    if args.wait:
        status = client.wait_until_finished(job_id, timeout_s=args.timeout)
        print(f"{job_id}: {status}")
        print(client.get_job_logs(job_id), end="")
        return 0 if status == "SUCCEEDED" else 1
    return 0


def cmd_list(args) -> int:
    from ray_tpu.util import state as us

    _connect(args.address)
    fn = {
        "tasks": us.list_tasks,
        "actors": us.list_actors,
        "objects": us.list_objects,
        "workers": us.list_workers,
        "nodes": us.list_nodes,
    }[args.kind]
    print(json.dumps(fn(limit=args.limit), indent=2, default=str))
    return 0


def cmd_summary(args) -> int:
    from ray_tpu.util import state as us

    _connect(args.address)
    kind = getattr(args, "kind", "tasks") or "tasks"
    fn = {"tasks": us.summarize_tasks, "actors": us.summarize_actors,
          "objects": us.summarize_objects}[kind]
    print(json.dumps(fn(), indent=2))
    return 0


_MEM_UNITS = {"B": 1, "KB": 1024, "MB": 1024 ** 2, "GB": 1024 ** 3}


def _fmt_bytes(n, units: str) -> str:
    div = _MEM_UNITS.get(units, 1)
    if div == 1:
        return str(int(n or 0))
    return f"{(n or 0) / div:.2f}{units}"


def _render_memory_groups(summary: dict, group_by: str, sort_by: str,
                          units: str) -> list:
    """The `ray memory`-style grouped table (reference: `ray memory
    --group-by ...`): one row per callsite / node / state with object
    counts and live bytes, sorted by size (default) or count."""
    rows: list = []
    if group_by == "callsite":
        src = summary.get("groups") or {}
        items = [(site, g.get("count", 0), g.get("bytes", 0),
                  g.get("unawaited", 0),
                  ",".join(sorted(g.get("kinds") or {})))
                 for site, g in src.items()]
    elif group_by == "node":
        items = []
        for node, states in (summary.get("by_node") or {}).items():
            count = sum(s.get("count", 0) for s in states.values())
            size = sum(s.get("bytes", 0) for s in states.values())
            items.append((node, count, size, "",
                          ",".join(sorted(states))))
    else:  # state
        items = [(state, s.get("count", 0), s.get("bytes", 0), "", "")
                 for state, s in (summary.get("by_state") or {}).items()]
    items.sort(key=lambda r: r[1] if sort_by == "count" else r[2],
               reverse=True)
    label = group_by.upper()
    hdr = f"{label:58} {'OBJECTS':>8} {'SIZE':>12} {'UNAWAITED':>9} KINDS"
    rows.append(f"=== Grouped by {group_by} (sort: {sort_by}) ===")
    rows.append(hdr)
    rows.append("-" * len(hdr))
    for name, count, size, unawaited, kinds in items:
        rows.append(f"{str(name)[:58]:58} {count:>8} "
                    f"{_fmt_bytes(size, units):>12} {str(unawaited):>9} "
                    f"{kinds}")
    if not items:
        rows.append("(no census reports yet — owners report every "
                    "rpc_report_interval_s)")
    return rows


def _render_memory_leaks(suspects: list, units: str) -> list:
    rows = ["=== Leak suspects ==="]
    if not suspects:
        rows.append("(none)")
        return rows
    for s in suspects:
        where = s.get("callsite") or s.get("object_id") or "?"
        trend = s.get("trend_bytes")
        extra = f"  trend={trend}" if trend else ""
        rows.append(f"[{s.get('kind')}] {where}  "
                    f"bytes={_fmt_bytes(s.get('bytes', 0), units)}  "
                    f"owner={s.get('owner', '')}  "
                    f"{s.get('detail', '')}{extra}")
    return rows


def _render_lineage(chain: dict, indent: int = 0) -> list:
    rows = []
    pad = "  " * indent
    task = chain.get("task")
    if task is None:
        rows.append(f"{pad}{chain.get('object_id')}  (no lineage "
                    f"recorded — put() or evicted entry)")
        return rows
    rows.append(f"{pad}{chain.get('object_id')}  <- task "
                f"{task.get('name')} [{task.get('task_id')}] "
                f"{task.get('state') or ''} on {task.get('node_id') or '?'}")
    for arg in chain.get("args") or ():
        rows.extend(_render_lineage(arg, indent + 1))
    if chain.get("args_truncated"):
        rows.append(f"{pad}  ... {chain['args_truncated']} more arg(s)")
    return rows


def cmd_memory(args) -> int:
    """Cluster memory report (reference: `ray memory` —
    _private/internal_api.py memory_summary): callsite-grouped live-ref
    census, per-object table, shm-store pin/fragmentation stats, leak
    suspects, and per-object lineage drill-down."""
    from ray_tpu.util import state as us

    _connect(args.address)
    as_json = args.json or getattr(args, "format", None) == "json"
    units = getattr(args, "units", "B") or "B"
    if getattr(args, "object_id", None):
        obj = us.get_object(args.object_id)
        if obj is None:
            print(f"object {args.object_id} not found (freed and no "
                  f"lineage recorded)")
            return 1
        if as_json:
            print(json.dumps({"object": obj}, indent=2, default=str))
            return 0
        print(f"object   {obj.get('object_id')}")
        for key in ("state", "size", "owner", "node_id", "callsite",
                    "refcount", "borrowers", "task_pins",
                    "container_pins", "read_pins", "reads", "age_s",
                    "owner_resident", "task_id"):
            if obj.get(key) not in (None, [], {}):
                print(f"{key:14} {obj[key]}")
        print("lineage:")
        for ln in _render_lineage(obj.get("lineage") or
                                  {"object_id": obj.get("object_id")}, 1):
            print(ln)
        return 0
    objs = us.list_objects(limit=args.limit)
    stats = us.object_store_stats()
    summary = us.memory_summary()
    if as_json:
        print(json.dumps({"objects": objs, "store": stats,
                          "summary": summary,
                          "leaks": summary.get("leak_suspects") or []},
                         indent=2, default=str))
        return 0
    group_by = getattr(args, "group_by", "callsite") or "callsite"
    sort_by = getattr(args, "sort_by", "size") or "size"
    for ln in _render_memory_groups(summary, group_by, sort_by, units):
        print(ln)
    print()
    hdr = f"{'OBJECT ID':42} {'STATE':10} {'SIZE':>12} {'REFS':>5} " \
          f"{'PINS':>5} {'OWNER':18} CALLSITE"
    print(hdr)
    print("-" * len(hdr))
    key = (lambda o: int(o.get("size") or 0)) if sort_by == "size" \
        else (lambda o: o.get("created_at") or 0)
    total = 0
    for o in sorted(objs, key=key, reverse=True):
        size = int(o.get("size") or 0)
        total += size
        pins = int(o.get("container_pins") or 0) + int(o.get("task_pins")
                                                       or 0)
        print(f"{o['object_id']:42} {o['state']:10} "
              f"{_fmt_bytes(size, units):>12} "
              f"{o.get('refcount', 0):>5} {pins:>5} "
              f"{str(o.get('owner', ''))[:18]:18} "
              f"{o.get('callsite', '')}")
    print(f"\n{len(objs)} objects, {total} bytes referenced; store: "
          f"{stats.get('in_use', 0)}/{stats.get('capacity', 0)} "
          f"bytes used, {stats.get('num_objects', 0)} resident, "
          f"{_fmt_bytes(stats.get('pinned_bytes', 0), units)} pinned / "
          f"{_fmt_bytes(stats.get('reclaimable_bytes', 0), units)} "
          f"reclaimable, {stats.get('eviction_candidates', 0)} eviction "
          f"candidate(s), {_fmt_bytes(stats.get('fragmented_free', 0), units)} "
          f"fragmented free")
    suspects = summary.get("leak_suspects") or []
    if suspects or getattr(args, "leaks", False):
        print()
        for ln in _render_memory_leaks(suspects, units):
            print(ln)
    return 0


def cmd_logs(args) -> int:
    """List or tail cluster worker logs (reference: `ray logs [file]`).
    --node routes through that node's agent (remote-node log access);
    --trace greps every log on every node for one request's
    [trace=<id>]-stamped lines (trace-correlated logs)."""
    from ray_tpu._private.worker_context import global_runtime

    _connect(args.address)
    conn = global_runtime().conn
    node_id = getattr(args, "node", None)
    base = {"node_id": node_id} if node_id else {}
    if getattr(args, "trace", None):
        return _grep_trace_logs(conn, args)
    if not args.name:
        reply = conn.call("log_index", dict(base))
        if reply.get("error"):
            print(reply["error"], file=sys.stderr)
            return 1
        for e in reply["logs"]:
            print(f"{e['bytes']:>10}  {e['name']}")
        return 0
    reply = conn.call("log_tail", {"name": args.name,
                                   "max_bytes": args.max_bytes, **base})
    if reply.get("error"):
        print(reply["error"], file=sys.stderr)
        return 1
    lines = reply["lines"][-args.tail:] if args.tail > 0 else []
    for ln in lines:
        print(ln)
    return 0


def _grep_trace_logs(conn, args) -> int:
    """Client-side grep for one trace's log lines: walk the head's
    session logs plus every node agent's log dir, tail each file, and
    keep the [trace=<id>]-prefixed lines (stamped by the workers'
    logging filter while a traced task executes)."""
    from ray_tpu.util import state as us

    needle = f"[trace={args.trace}]"
    sources = [(None, "head")]
    try:
        sources += [(n["node_id"], n["node_id"]) for n in us.list_nodes()]
    except Exception:
        pass
    hits = 0
    for node_id, label in sources:
        body = {"node_id": node_id} if node_id else {}
        try:
            index = conn.call("log_index", dict(body)).get("logs") or []
        except Exception:
            continue
        for e in index:
            reply = conn.call("log_tail", {
                "name": e["name"], "max_bytes": args.max_bytes, **body})
            for ln in reply.get("lines") or []:
                if needle in ln:
                    print(f"{label}/{e['name']}: {ln}")
                    hits += 1
    if not hits:
        print(f"no log lines found for trace {args.trace}")
    return 0


def cmd_trace(args) -> int:
    """Causal trace waterfall (`ray-tpu trace <id>`), or the retained
    trace list with no id. --perfetto exports one trace as a Chrome
    JSON trace (open in Perfetto / chrome://tracing) with one row per
    process and proper parent nesting."""
    from ray_tpu.util import state as us

    _connect(args.address)
    if not args.trace_id:
        rows = us.list_traces(limit=args.limit,
                              exemplars_only=args.exemplars)
        if not rows:
            print("no traces retained")
            return 0
        print(f"{'TRACE':<34} {'ROOT':<24} {'SPANS':>5} "
              f"{'DUR_MS':>8}  FLAGS")
        for r in rows:
            flags = ",".join(f for f in ("error", "shed", "slow")
                             if r.get(f)) or "-"
            print(f"{r['trace_id']:<34} {r.get('root') or '?':<24} "
                  f"{r['spans']:>5} {r['duration_s'] * 1e3:>8.1f}  "
                  f"{flags}")
        return 0
    tr = us.get_trace(args.trace_id)
    if tr is None:
        print(f"no trace {args.trace_id!r} retained (folded, or never "
              f"sampled — see `ray-tpu trace` for the retained set)")
        return 1
    spans = tr.get("spans_detail") or []
    if args.perfetto:
        _write_perfetto(args.perfetto, tr, spans)
        print(f"wrote {args.perfetto}")
        return 0
    flags = ",".join(f for f in ("error", "shed", "slow")
                     if tr.get(f)) or "-"
    print(f"trace {tr['trace_id']}  root={tr.get('root') or '?'}  "
          f"spans={tr['spans']}  dur={tr['duration_s'] * 1e3:.1f}ms  "
          f"flags={flags}")
    _print_waterfall(spans, tr.get("start") or 0.0,
                     max(tr.get("duration_s") or 0.0, 1e-9))
    return 0


def _print_waterfall(spans: list, t0: float, total: float) -> None:
    """Indented causal tree, one line per span, with an offset/duration
    bar scaled to the trace: `<indent><name> [pid/node] |--=====--|`."""
    by_id = {s["span_id"]: s for s in spans}
    children: dict = {}
    roots = []
    for s in spans:
        p = s.get("parent_span_id") or ""
        if p and p in by_id:
            children.setdefault(p, []).append(s)
        else:
            roots.append(s)
    width = 40

    def bar(s):
        off = int((max(0.0, s["start"] - t0) / total) * width)
        dur = max(1, int(((s["end"] - s["start"]) / total) * width))
        off = min(off, width - 1)
        dur = min(dur, width - off)
        return "." * off + "=" * dur + "." * (width - off - dur)

    def walk(s, depth):
        where = s.get("worker_id") or s.get("node_id") \
            or (f"pid:{s['pid']}" if s.get("pid") else "?")
        ms = (s["end"] - s["start"]) * 1e3
        mark = " FAILED" if s.get("failed") else ""
        print(f"  {'  ' * depth}{s.get('name'):<{30 - 2 * min(depth, 8)}}"
              f" |{bar(s)}| {ms:>8.1f}ms  [{s.get('kind', '?')}"
              f" {where}]{mark}")
        for c in sorted(children.get(s["span_id"], []),
                        key=lambda x: x["start"]):
            walk(c, depth + 1)

    for r in sorted(roots, key=lambda x: x["start"]):
        walk(r, 0)


def _write_perfetto(path: str, tr: dict, spans: list) -> None:
    """Chrome JSON trace: complete ("X") events, one pid row per
    process, span hierarchy recoverable via the id args."""
    events = []
    for s in spans:
        events.append({
            "name": s.get("name"),
            "cat": s.get("kind", "span"),
            "ph": "X",
            "ts": s["start"] * 1e6,
            "dur": max(0.0, (s["end"] - s["start"]) * 1e6),
            "pid": s.get("pid") or 0,
            "tid": s.get("worker_id") or s.get("task_id") or 0,
            "args": {k: s.get(k) for k in
                     ("span_id", "parent_span_id", "task_id",
                      "worker_id", "node_id", "attributes", "failed")
                     if s.get(k) is not None},
        })
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms",
                   "otherData": {"trace_id": tr["trace_id"]}}, f)


def cmd_crashes(args) -> int:
    """Post-mortem crash reports (`ray-tpu crashes [worker_id]`):
    classified worker/node deaths from the head's forensics table;
    a worker_id argument prints the full report (stack excerpt, log
    tail, beacon)."""
    from ray_tpu.util import state as us

    _connect(args.address)
    if args.worker_id:
        report = us.get_crash_report(args.worker_id)
        if report is None:
            print(f"no crash report for {args.worker_id}")
            return 1
        if args.json:
            print(json.dumps(report, indent=2, default=str))
            return 0
        print(f"worker   {report.get('worker_id')}  "
              f"(pid {report.get('pid')}, node {report.get('node_id')})")
        print(f"reason   {report.get('exit_type')}: "
              f"{report.get('exit_detail')}")
        sig = report.get("signal_name") or report.get("term_signal")
        print(f"status   exit_code={report.get('exit_code')} "
              f"signal={sig}")
        lt = report.get("last_task")
        if lt:
            print(f"last task  {lt.get('name')} [{lt.get('task_id')}]")
        if report.get("beacon"):
            print(f"beacon   {json.dumps(report['beacon'])}")
        prof = report.get("profile")
        if prof:
            # Profiling-plane sidecar: the worker's last sampled window
            # — "what it was burning CPU on" at the end of its life.
            print(f"\n--- last profile window ({prof.get('samples', 0)} "
                  f"samples, {prof.get('role', 'worker')}) ---")
            top = sorted((prof.get("folded") or {}).items(),
                         key=lambda kv: -kv[1])[:8]
            for stack, hits in top:
                label = stack if len(stack) <= 90 else "…" + stack[-89:]
                print(f"  {hits:>6}  {label}")
        for title, key in (("post-mortem stack", "stack"),
                           ("log tail", "log_tail")):
            lines = report.get(key) or []
            if lines:
                print(f"\n--- {title} ---")
                for ln in lines:
                    print(f"  {ln}")
        return 0
    rows = us.list_crash_reports(limit=args.limit)
    if args.json:
        print(json.dumps(rows, indent=2, default=str))
        return 0
    hdr = f"{'WORKER':24} {'NODE':16} {'REASON':20} {'SIG/CODE':>8} " \
          f"{'LAST TASK':24} DETAIL"
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        sig = r.get("signal_name") or r.get("exit_code")
        lt = (r.get("last_task") or {}).get("name") or ""
        print(f"{r.get('worker_id', ''):24} {r.get('node_id') or '':16} "
              f"{r.get('exit_type', ''):20} {str(sig if sig is not None else ''):>8} "
              f"{lt:24} {r.get('exit_detail', '')}")
    print(f"\n{len(rows)} report(s)")
    return 0


def _merged_folded(windows: list, cap: int = 4096) -> dict:
    from ray_tpu._private import profplane

    merged: dict = {}
    for w in windows:
        profplane.merge_folded(merged, w.get("folded") or {}, cap=cap)
    return merged


def _print_folded(folded: dict, top: int, total_hint=None) -> None:
    total = total_hint if total_hint is not None else \
        sum(abs(v) for v in folded.values()) or 1
    width = 30
    rows = sorted(folded.items(), key=lambda kv: -abs(kv[1]))[:top]
    for stack, hits in rows:
        share = abs(hits) / total
        bar = "#" * max(1, int(share * width)) if hits else ""
        # Deep stacks: keep the leafward frames (where the time IS).
        label = stack if len(stack) <= 100 else "…" + stack[-99:]
        val = f"{hits:+.2%}" if isinstance(hits, float) else f"{hits:>6}"
        print(f"  {val}  {bar:<{width}}  {label}")


def cmd_profile(args) -> int:
    """Continuous-profiling plane (`ray-tpu profile`): render the
    head's merged cluster profile table as a text flamegraph summary —
    always-on duty-cycled samples from every runtime process, merged
    by (node, role, window). `--diff A B` prints the differential
    folded output between two window indexes (per-sample share, so a
    busy and a quiet window compare honestly)."""
    from ray_tpu._private import profplane
    from ray_tpu.util import state as us

    _connect(args.address)
    prof = us.cluster_profile(role=args.role, node=args.node,
                              window=args.window)
    windows = prof.get("windows") or []
    if args.json:
        print(json.dumps(prof, indent=2, default=str))
        return 0
    if args.diff:
        a_win, b_win = (int(x) for x in args.diff)
        a = _merged_folded([w for w in windows if w["window"] == a_win])
        b = _merged_folded([w for w in windows if w["window"] == b_win])
        if not a or not b:
            print(f"no profile data for window "
                  f"{a_win if not a else b_win}")
            return 1
        d = profplane.diff_folded(a, b)
        print(f"differential profile: window {a_win} -> {b_win} "
              f"(signed per-sample share; + = grew)")
        _print_folded(d, args.top, total_hint=1.0)
        return 0
    if not windows:
        print("no profile windows yet (plane disabled via "
              "RAY_TPU_PROFILING_ENABLED=0, or no window elapsed — "
              "windows ship every profiling_window_s on the amortized "
              "report casts)")
        return 1
    merged = _merged_folded(windows)
    if args.output:
        with open(args.output, "w") as f:
            for stack, hits in merged.items():
                f.write(f"{stack} {hits}\n")
        print(f"wrote {len(merged)} collapsed stacks to {args.output}")
    if args.speedscope:
        us.save_speedscope({"folded": merged, "worker_id": "cluster"},
                           args.speedscope, name="ray_tpu cluster")
        print(f"wrote speedscope profile to {args.speedscope}")
    if args.output or args.speedscope:
        return 0
    stats = prof.get("stats") or {}
    roles = sorted({w["role"] for w in windows})
    nodes = sorted({w["node"] for w in windows})
    pids = sorted({p for w in windows for p in (w.get("pids") or ())})
    samples = sum(w.get("samples") or 0 for w in windows)
    cost = sum(w.get("sample_cost_s") or 0.0 for w in windows)
    print(f"cluster profile: {len(windows)} window(s), {samples} samples "
          f"across {len(pids)} pid(s)  [roles: {', '.join(roles)};"
          f" nodes: {', '.join(nodes)}]")
    print(f"  plane: {stats.get('windows_total', 0)} windows merged, "
          f"{stats.get('dropped_windows', 0)} evicted, "
          f"{stats.get('pinned', 0)} pinned (phase regressions), "
          f"{stats.get('gil_exemplars', 0)} GIL exemplars; "
          f"sampling cost {cost:.3f}s")
    pinned = [w for w in windows if w.get("pinned")]
    for w in pinned:
        pin = w["pinned"]
        print(f"  PINNED window {w['window']} ({w['role']}@{w['node']}): "
              f"{pin['phase']} p95 {pin['p95'] * 1e3:.1f}ms vs trailing "
              f"median {pin['trailing_median'] * 1e3:.1f}ms")
    print("\ntop self-time frames (leaf hits):")
    _print_folded(profplane.self_time(merged), args.top)
    print("\ntop stacks:")
    _print_folded(merged, args.top)
    exemplars = prof.get("gil_exemplars") or []
    if exemplars:
        print("\nGIL-starvation exemplars (wall >> cpu tasks):")
        for ex in exemplars[-5:]:
            print(f"  {ex.get('name')} [{(ex.get('task_id') or '')[:16]}] "
                  f"wall {ex.get('wall_s')}s cpu {ex.get('cpu_s')}s "
                  f"({ex.get('role')}@{ex.get('node')})")
    return 0


def cmd_lint(args) -> int:
    """Invariant analysis (`ray-tpu lint`): the tools/rtlint static
    cross-checkers — wire-protocol kinds vs dispatch tables, env knobs
    vs the config registry, lock discipline and lock-order cycles,
    wall/monotonic clock splits, metric catalog + label cardinality,
    and the direct-plane head-frame budget. Exit 0 means every
    invariant holds (modulo the written baseline); findings exit 1
    with file:line callsites. Catalog: docs/INVARIANTS.md."""
    import os

    try:
        from tools.rtlint.__main__ import main as lint_main
    except ImportError:
        # running from an installed wheel won't find the repo-root
        # `tools` package on sys.path; a source checkout will.
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if not os.path.isdir(os.path.join(root, "tools", "rtlint")):
            print("ray-tpu lint runs against a source checkout "
                  "(tools/rtlint is not shipped in wheels)",
                  file=sys.stderr)
            return 2
        sys.path.insert(0, root)
        from tools.rtlint.__main__ import main as lint_main

    argv: list[str] = []
    if args.root is not None:
        argv += ["--root", args.root]
    if args.baseline is not None:
        argv += ["--baseline", args.baseline]
    for name in args.passes or ():
        argv += ["--pass", name]
    argv += ["--format", args.format]
    if args.write_baseline:
        argv += ["--write-baseline", args.write_baseline]
    return lint_main(argv)


def cmd_health(args) -> int:
    """Overload / retry-plane health view (`ray-tpu health`): pending
    budgets, deadline sheds, admission rejections, memory-pressured
    nodes, and per-target circuit breakers (open state + trip history)
    so an operator can see why traffic to a peer is being shed."""
    import time as _time

    from ray_tpu.util import state as us

    _connect(args.address)
    h = us.health_summary()
    if args.json:
        print(json.dumps(h, indent=2, default=str))
        return 0
    g = h["gauges"]
    print(f"nodes alive      {g.get('nodes_alive', '?')}  "
          f"(pressured: {g.get('mem_pressured_nodes', 0)})")
    print(f"workers alive    {g.get('workers_alive', '?')}")
    print(f"tasks pending    {g.get('admission_pending_total', 0)} "
          f"across {g.get('admission_pending_owners', 0)} owner(s)")
    print(f"admission        {h['counters'].get('admission_rejected', 0)} "
          f"rejected")
    if h["tasks_shed"]:
        shed = ", ".join(f"{k}={v}" for k, v in
                         sorted(h["tasks_shed"].items()))
        print(f"deadline sheds   {shed}")
    for nid, info in h["pressured_nodes"].items():
        used, total = info.get("used") or 0, info.get("total") or 0
        pct = f"{100.0 * used / total:.0f}%" if total else "?"
        print(f"PRESSURED node   {nid}  mem {pct} ({used}/{total})")
    if h["worker_deaths"]:
        deaths = ", ".join(f"{k}={v}" for k, v in
                           sorted(h["worker_deaths"].items()))
        print(f"worker deaths    {deaths}")
    if not h["breakers"]:
        print("breakers         all closed, no trips")
    for scope, table in h["breakers"].items():
        for target, b in table.items():
            age = b.get("last_trip_at")
            ago = (f"{_time.time() - age:.0f}s ago"
                   if age else "never")
            state = "OPEN" if b.get("open") else "closed"
            print(f"breaker          [{scope}] {target}: {state}, "
                  f"{b.get('trip_count', 0)} trip(s), last {ago}, "
                  f"{b.get('failures', 0)} consecutive failure(s)")
    return 0


_SPARK_BARS = "▁▂▃▄▅▆▇█"


def _sparkline(values: "list[float]", width: int = 24) -> str:
    """Tiny block-char sparkline (fixed palette, no deps). Values are
    resampled to ``width`` columns and scaled to the window's max."""
    vals = [v for v in values if v is not None]
    if not vals:
        return ""
    if len(vals) > width:
        # Tail-biased resample: the most recent samples matter most.
        stride = len(vals) / width
        vals = [vals[min(len(vals) - 1, int(i * stride))]
                for i in range(width)]
    hi = max(vals)
    lo = min(vals)
    span = (hi - lo) or 1.0
    return "".join(
        _SPARK_BARS[int((v - lo) / span * (len(_SPARK_BARS) - 1))]
        for v in vals)


def _counter_rates(series: "list[dict]") -> "list[float]":
    """Per-bucket rates from a query_metrics counter reply (summed
    across matching series, consecutive-bucket deltas / dt)."""
    buckets: dict[float, float] = {}
    for s in series:
        for b in s.get("points") or ():
            buckets[b[0]] = buckets.get(b[0], 0.0) + b[5]
    ordered = sorted(buckets.items())
    rates = []
    for (t0, v0), (t1, v1) in zip(ordered, ordered[1:]):
        dt = t1 - t0
        if dt > 0:
            rates.append(max(0.0, v1 - v0) / dt)
    return rates


def cmd_top(args) -> int:
    """Live cluster view (`ray-tpu top`): one refreshing screen with
    nodes, tasks/s (with history sparkline from the embedded
    tsdb), phase p95s, firing alerts, and the hottest flamegraph leaf
    from the continuous profiler — the "is the cluster healthy right
    now" answer without a dashboard deployment."""
    import time as _time

    from ray_tpu._private.worker_context import global_runtime
    from ray_tpu.util import state as us

    _connect(args.address)
    iterations = 1 if args.once else (args.iterations or 0)
    shown = 0
    while True:
        snap = global_runtime().conn.call("runtime_stats", {},
                                          timeout=10)
        rate_q = us.query_metrics("ray_tpu_tasks_finished_total",
                                  start=_time.time() - 600)
        p95_q = us.query_metrics("ray_tpu_phase_p95_seconds",
                                 start=_time.time() - 120)
        load_q = us.query_metrics("ray_tpu_node_load1",
                                  start=_time.time() - 120)
        alerts = us.list_alerts()
        if args.json:
            print(json.dumps({
                "gauges": snap.get("gauges"),
                "counters": snap.get("counters"),
                "tasks_shed": snap.get("tasks_shed"),
                "telemetry": snap.get("telemetry"),
                "alerts": alerts,
                "tasks_per_s": _counter_rates(
                    rate_q.get("series") or []),
            }, indent=2, default=str))
        else:
            if not args.once and shown:
                print("\x1b[2J\x1b[H", end="")
            _render_top(snap, rate_q, p95_q, load_q, alerts)
        shown += 1
        if iterations and shown >= iterations:
            return 0
        try:
            _time.sleep(max(0.2, args.interval))
        except KeyboardInterrupt:
            return 0


def _render_top(snap: dict, rate_q: dict, p95_q: dict, load_q: dict,
                alerts: dict) -> None:
    import time as _time

    g = snap.get("gauges") or {}
    c = snap.get("counters") or {}
    print(f"ray-tpu top — {_time.strftime('%H:%M:%S')}")
    print(f"nodes {g.get('nodes_alive', '?')} "
          f"(pressured {g.get('mem_pressured_nodes', 0)})   "
          f"workers {g.get('workers_alive', '?')}   "
          f"actors {g.get('actors_alive', '?')}   "
          f"pending {g.get('tasks_pending', 0)}")
    rates = _counter_rates(rate_q.get("series") or [])
    spark = _sparkline(rates)
    now_rate = rates[-1] if rates else 0.0
    shed = sum((snap.get("tasks_shed") or {}).values())
    print(f"tasks: {c.get('tasks_finished', 0)} finished "
          f"({now_rate:.1f}/s {spark}), "
          f"{c.get('tasks_failed', 0)} failed, {shed} shed")
    p95s = []
    for s in (p95_q.get("series") or []):
        pts = s.get("points") or []
        if pts:
            phase = (s.get("labels") or {}).get("phase", "?")
            p95s.append(f"{phase} {pts[-1][5] * 1e3:.1f}ms")
    if p95s:
        print(f"phase p95: {'  '.join(sorted(p95s))}")
    tele = snap.get("telemetry") or {}
    print(f"tsdb: {tele.get('series', 0)} series, "
          f"{tele.get('points', 0)} points retained "
          f"({tele.get('dropped_total', 0)} folded)")
    firing = [a for a in (alerts.get("alerts") or [])
              if a.get("state") == "firing"]
    if firing:
        for a in firing:
            print(f"ALERT [{a.get('severity')}] {a.get('name')} "
                  f"value={a.get('value')} — {a.get('summary', '')}")
    else:
        print("alerts: none firing")
    # Hottest self-time leaf across roles (the continuous profiler's
    # one-line answer to "what is the cluster busy doing").
    best = ("", "", 0)
    for role, frames in ((snap.get("profiling") or {})
                         .get("self_time") or {}).items():
        for frame, hits in frames.items():
            if hits > best[2]:
                best = (role, frame, hits)
    if best[2]:
        print(f"top flame leaf: {best[1]} ({best[0]}, {best[2]} hits)")
    loads = []
    for s in (load_q.get("series") or []):
        pts = s.get("points") or []
        if pts:
            nid = (s.get("labels") or {}).get("node_id", "?")
            loads.append(f"  {nid}  load1 {pts[-1][5]:.2f}")
    if loads:
        print("nodes:")
        for row in sorted(loads):
            print(row)


def cmd_alerts(args) -> int:
    """SLO alert table (`ray-tpu alerts`): active pending/firing
    records, `--history` adds the resolved ring. Firing rows print the
    cross-plane evidence pinned at fire time (trace exemplars, profile
    windows, crash reports)."""
    import time as _time

    from ray_tpu.util import state as us

    _connect(args.address)
    reply = us.list_alerts(history=args.history)
    if args.format == "json":
        print(json.dumps(reply, indent=2, default=str))
        return 0
    rows = reply.get("alerts") or []
    stats = reply.get("stats") or {}
    if not reply.get("enabled", True):
        print("alert engine disabled (RAY_TPU_ALERTS_ENABLED=0)")
        return 0
    print(f"{stats.get('rules', 0)} rule(s): "
          f"{stats.get('firing', 0)} firing, "
          f"{stats.get('pending', 0)} pending, "
          f"{stats.get('fired_total', 0)} fired / "
          f"{stats.get('resolved_total', 0)} resolved lifetime")
    if not rows:
        print("no active alerts" + ("" if args.history
                                    else " (--history for resolved)"))
        return 0
    now = _time.time()
    for a in rows:
        at = a.get("fired_at") or a.get("since")
        ago = f"{now - at:.0f}s ago" if at else "?"
        print(f"[{a.get('state', '?'):8}] {a.get('severity', '?'):4} "
              f"{a.get('name')}  value={a.get('value')}  ({ago})")
        if a.get("summary"):
            print(f"           {a['summary']}")
        ctx = a.get("context") or {}
        if ctx.get("trace_exemplars"):
            print(f"           traces: "
                  f"{', '.join(ctx['trace_exemplars'][:4])}")
        if ctx.get("profile_windows"):
            wins = ctx["profile_windows"]
            print(f"           profile windows: {len(wins)} overlapping "
                  f"(e.g. {wins[-1]['role']}@{wins[-1]['node']} "
                  f"window {wins[-1]['window']})")
        if ctx.get("crash_reports"):
            print(f"           crashes in window: "
                  f"{len(ctx['crash_reports'])}")
    return 0


def cmd_metrics(args) -> int:
    """Telemetry-history queries (`ray-tpu metrics query NAME`): range
    reads from the head's embedded tsdb — raw ~10s buckets for the
    last 30min, 1min rollups for 24h."""
    import time as _time

    from ray_tpu.util import state as us

    _connect(args.address)
    if args.metrics_cmd != "query":
        raise SystemExit(f"unknown metrics command {args.metrics_cmd!r}")
    labels = {}
    for kv in args.label or ():
        k, _, v = kv.partition("=")
        labels[k] = v
    start = args.start if args.start is not None else \
        _time.time() - args.window
    reply = us.query_metrics(args.name, labels or None, start,
                             args.end, args.step)
    if args.format == "json":
        print(json.dumps(reply, indent=2, default=str))
        return 0
    series = reply.get("series") or []
    if not reply.get("enabled", True):
        print("telemetry store disabled (RAY_TPU_TSDB_ENABLED=0)")
        return 0
    if not series:
        print(f"no retained points for {args.name!r} in the window")
        return 1
    for s in series:
        pts = s.get("points") or []
        if not pts:
            continue
        label = ",".join(f"{k}={v}" for k, v in
                         sorted((s.get("labels") or {}).items()))
        vals = [b[5] for b in pts]
        print(f"{s['name']}{{{label}}}  [{s.get('kind')}] "
              f"{len(pts)} bucket(s) @ {s.get('resolution_s', 0):.0f}s")
        print(f"  last={vals[-1]:.6g} min={min(b[1] for b in pts):.6g} "
              f"max={max(b[2] for b in pts):.6g}  {_sparkline(vals)}")
    return 0


def cmd_stop(args) -> int:
    """Stop the cluster: all agents, then the head (reference: `ray
    stop`)."""
    from ray_tpu._private.worker_context import global_runtime

    _connect(args.address)
    reply = global_runtime().conn.call("stop_cluster", {})
    print(f"stopping head + {reply['agents']} node agent(s)")
    return 0


def cmd_timeline(args) -> int:
    from ray_tpu.util import state as us

    _connect(args.address)
    path = us.timeline(args.output)
    print(f"wrote {path}")
    return 0


def cmd_dashboard(args) -> int:
    from ray_tpu.dashboard import start_dashboard

    _connect(args.address)
    port = start_dashboard(port=args.port)
    print(f"dashboard at http://127.0.0.1:{port}/")
    import threading

    threading.Event().wait()
    return 0


def cmd_job(args) -> int:
    """`ray-tpu job ...` (reference: dashboard/modules/job/cli.py —
    ray job submit/status/logs/stop/list)."""
    from ray_tpu.job_submission import JobSubmissionClient

    if args.job_cmd == "submit":
        return cmd_submit(args)  # same namespace shape; one implementation
    client = JobSubmissionClient(address=args.address)
    if args.job_cmd == "status":
        info = client.get_job_info(args.job_id)
        print(json.dumps(info, indent=2, default=str))
        return 0
    if args.job_cmd == "logs":
        print(client.get_job_logs(args.job_id), end="")
        return 0
    if args.job_cmd == "stop":
        stopped = client.stop_job(args.job_id)
        print("stopped" if stopped else "not running")
        return 0
    if args.job_cmd == "list":
        for info in client.list_jobs():
            print(f"{info.get('job_id')}\t{info.get('status')}\t"
                  f"{info.get('entrypoint', '')[:60]}")
        return 0
    raise SystemExit(f"unknown job command {args.job_cmd!r}")


def cmd_serve(args) -> int:
    """`ray-tpu serve ...` (reference: serve/scripts.py — serve
    deploy/status/shutdown)."""
    from ray_tpu import serve

    _connect(args.address)
    if args.serve_cmd == "deploy":
        serve.run_from_config(args.config_file)
        print(f"deployed from {args.config_file}")
        st = serve.status()
        for name, info in st.items():
            print(f"  {name}: {info['running_replicas']}/"
                  f"{info['target_replicas']} replicas")
        return 0
    if args.serve_cmd == "run":
        # `serve run pkg.mod:app` (reference: serve/scripts.py run —
        # deploy an import path; `:` splits module from attribute).
        import importlib

        target = args.import_path
        mod_name, _, attr = target.partition(":")
        if not attr:
            raise SystemExit(
                f"import path must be 'module:attribute', got {target!r}")
        from ray_tpu.serve.deployment import Application, Deployment

        app = getattr(importlib.import_module(mod_name), attr)
        if not isinstance(app, (Application, Deployment)) and callable(app):
            # A builder function (e.g. build_openai_app-style) — only
            # zero-arg builders are runnable from the CLI.
            import inspect as _inspect

            sig = _inspect.signature(app)
            required = [p for p in sig.parameters.values()
                        if p.default is p.empty
                        and p.kind not in (p.VAR_POSITIONAL, p.VAR_KEYWORD)]
            if required:
                raise SystemExit(
                    f"{target!r} is a builder with required arguments; "
                    "deploy it via a config file instead")
            app = app()
        serve.run(app, route_prefix=args.route_prefix)
        print(f"running {target}")
        if args.blocking:
            import time as _time

            while True:
                _time.sleep(3600)
        return 0
    if args.serve_cmd == "status":
        print(json.dumps(serve.status(), indent=2, default=str))
        return 0
    if args.serve_cmd == "shutdown":
        serve.shutdown()
        print("serve shut down")
        return 0
    raise SystemExit(f"unknown serve command {args.serve_cmd!r}")


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="ray-tpu", description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start a head or join as a node")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--snapshot-path", default=None,
                    help="head FT: snapshot file for durable state")
    sp.add_argument("--external-store", default=None,
                    help="head HA: shared store URI (file:///dir) — a "
                         "fresh head on any node restores cluster state")
    sp.add_argument("--address", default=None, help="join an existing head")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=6380)
    sp.add_argument("--num-cpus", type=float, default=None)
    sp.add_argument("--num-tpus", type=float, default=None)
    sp.add_argument("--resources", default=None, help="JSON dict")
    sp.add_argument("--object-store-memory", type=int, default=None)
    sp.add_argument("--node-id", default=None)
    sp.add_argument("--force-remote-objects", action="store_true",
                    help=argparse.SUPPRESS)  # test hook
    sp.set_defaults(fn=cmd_start)

    s = sub.add_parser("status")
    s.add_argument("--address", required=True)
    s.set_defaults(fn=cmd_status)

    s = sub.add_parser("summary")
    s.add_argument("kind", nargs="?", default="tasks",
                   choices=["tasks", "actors", "objects"])
    s.add_argument("--address", required=True)
    s.set_defaults(fn=cmd_summary)

    s = sub.add_parser("submit", help="run an entrypoint as a cluster job")
    s.add_argument("--address", required=True)
    s.add_argument("--wait", action="store_true")
    s.add_argument("--timeout", type=float, default=600.0)
    s.add_argument("entrypoint", nargs=argparse.REMAINDER)
    s.set_defaults(fn=cmd_submit)

    s = sub.add_parser("list")
    s.add_argument("kind", choices=["tasks", "actors", "objects", "workers", "nodes"])
    s.add_argument("--address", required=True)
    s.add_argument("--limit", type=int, default=100)
    s.set_defaults(fn=cmd_list)

    s = sub.add_parser("memory",
                       help="cluster memory report: callsite-grouped "
                            "census, leak suspects, lineage drill-down")
    s.add_argument("object_id", nargs="?", default=None,
                   help="drill into one object (full row + lineage)")
    s.add_argument("--address", required=True)
    s.add_argument("--limit", type=int, default=200)
    s.add_argument("--json", action="store_true")
    s.add_argument("--format", choices=["table", "json"], default="table")
    s.add_argument("--group-by", dest="group_by", default="callsite",
                   choices=["callsite", "node", "state"])
    s.add_argument("--sort-by", dest="sort_by", default="size",
                   choices=["size", "count"])
    s.add_argument("--units", default="B",
                   choices=["B", "KB", "MB", "GB"])
    s.add_argument("--leaks", action="store_true",
                   help="always print the leak-suspect section")
    s.set_defaults(fn=cmd_memory)

    s = sub.add_parser("trace",
                       help="request-trace waterfall / list / export")
    s.add_argument("trace_id", nargs="?", default=None,
                   help="trace id (from X-Trace-Id / list); omit to list")
    s.add_argument("--address", required=True)
    s.add_argument("--limit", type=int, default=50)
    s.add_argument("--exemplars", action="store_true",
                   help="list only slow/error/shed exemplar traces")
    s.add_argument("--perfetto", default=None, metavar="FILE",
                   help="export the trace as Chrome/Perfetto JSON")
    s.set_defaults(fn=cmd_trace)

    s = sub.add_parser("logs", help="list or tail cluster worker logs")
    s.add_argument("name", nargs="?", default=None,
                   help="log name from the listing (omit to list)")
    s.add_argument("--address", required=True)
    s.add_argument("--tail", type=int, default=100)
    s.add_argument("--max-bytes", type=int, default=64 * 1024)
    s.add_argument("--node", default=None,
                   help="node id: list/tail that node's logs via its agent")
    s.add_argument("--trace", default=None,
                   help="trace id: grep all logs for the request's lines")
    s.set_defaults(fn=cmd_logs)

    s = sub.add_parser("crashes",
                       help="post-mortem worker crash reports")
    s.add_argument("worker_id", nargs="?", default=None,
                   help="print one full report (stacks, log tail, beacon)")
    s.add_argument("--address", required=True)
    s.add_argument("--limit", type=int, default=100)
    s.add_argument("--json", action="store_true")
    s.set_defaults(fn=cmd_crashes)

    s = sub.add_parser(
        "profile",
        help="merged cluster flamegraph from the always-on profiling "
             "plane (filter --role/--node/--window, diff windows, "
             "export collapsed stacks / speedscope)")
    s.add_argument("--address", required=True)
    s.add_argument("--role", default=None,
                   choices=["head", "agent", "worker", "driver"])
    s.add_argument("--node", default=None, help="node id filter")
    s.add_argument("--window", type=int, default=None,
                   help="window index filter (floor(ts / window_s))")
    s.add_argument("--diff", nargs=2, metavar=("A", "B"), default=None,
                   help="differential folded output between two "
                        "window indexes (per-sample share)")
    s.add_argument("--speedscope", default=None, metavar="FILE",
                   help="export merged profile as speedscope JSON")
    s.add_argument("-o", "--output", default=None, metavar="FILE",
                   help="write merged collapsed-stack lines "
                        "(flamegraph.pl input)")
    s.add_argument("--top", type=int, default=15)
    s.add_argument("--json", action="store_true")
    s.set_defaults(fn=cmd_profile)

    s = sub.add_parser("health",
                       help="overload + retry-plane health (budgets, "
                            "sheds, pressure, circuit breakers)")
    s.add_argument("--address", required=True)
    s.add_argument("--json", action="store_true")
    s.set_defaults(fn=cmd_health)

    s = sub.add_parser(
        "top",
        help="live cluster view: nodes, tasks/s with sparkline, phase "
             "p95s, firing alerts, hottest flamegraph leaf")
    s.add_argument("--address", required=True)
    s.add_argument("--interval", type=float, default=2.0,
                   help="refresh period in seconds")
    s.add_argument("--once", action="store_true",
                   help="render a single frame and exit")
    s.add_argument("--iterations", type=int, default=0,
                   help="exit after N frames (0 = until ^C)")
    s.add_argument("--json", action="store_true")
    s.set_defaults(fn=cmd_top)

    s = sub.add_parser(
        "alerts",
        help="SLO alert table from the burn-rate engine "
             "(--history adds resolved alerts)")
    s.add_argument("--address", required=True)
    s.add_argument("--history", action="store_true",
                   help="include the resolved-alert ring")
    s.add_argument("--format", choices=["table", "json"],
                   default="table")
    s.set_defaults(fn=cmd_alerts)

    s = sub.add_parser(
        "metrics",
        help="query the head's embedded metric history "
             "(raw 10s buckets 30min, 1min rollups 24h)")
    msub = s.add_subparsers(dest="metrics_cmd", required=True)
    m = msub.add_parser("query", help="range-query one series name")
    m.add_argument("name", help="series name, e.g. ray_tpu_phase_p95_seconds")
    m.add_argument("--address", required=True)
    m.add_argument("--label", action="append", metavar="K=V",
                   help="label filter (repeatable)")
    m.add_argument("--start", type=float, default=None,
                   help="unix start time (default: now - window)")
    m.add_argument("--end", type=float, default=None)
    m.add_argument("--step", type=float, default=None,
                   help="coalesce buckets to this resolution")
    m.add_argument("--window", type=float, default=600.0,
                   help="lookback seconds when --start is omitted")
    m.add_argument("--format", choices=["table", "json"],
                   default="table")
    s.set_defaults(fn=cmd_metrics)

    s = sub.add_parser(
        "lint",
        help="run the invariant cross-checkers (tools/rtlint): wire "
             "kinds, env knobs, locks, clocks, metrics, frame budget")
    s.add_argument("--root", default=None,
                   help="repo root to lint (default: this checkout)")
    s.add_argument("--baseline", default=None,
                   help="baseline.toml path ('' disables)")
    s.add_argument("--pass", dest="passes", action="append",
                   metavar="NAME", help="run only this pass (repeatable)")
    s.add_argument("--format", choices=("text", "json"), default="text")
    s.add_argument("--write-baseline", metavar="PATH")
    s.set_defaults(fn=cmd_lint)

    s = sub.add_parser("stop", help="stop all agents and the head")
    s.add_argument("--address", required=True)
    s.set_defaults(fn=cmd_stop)

    s = sub.add_parser("timeline")
    s.add_argument("--address", required=True)
    s.add_argument("-o", "--output", default="timeline.json")
    s.set_defaults(fn=cmd_timeline)

    s = sub.add_parser("dashboard")
    s.add_argument("--address", required=True)
    s.add_argument("--port", type=int, default=0)
    s.set_defaults(fn=cmd_dashboard)

    s = sub.add_parser("job", help="job submission (submit/status/logs/stop/list)")
    jsub = s.add_subparsers(dest="job_cmd", required=True)
    j = jsub.add_parser("submit")
    j.add_argument("--address", required=True)
    j.add_argument("--wait", action="store_true")
    j.add_argument("--timeout", type=float, default=600.0)
    j.add_argument("entrypoint", nargs=argparse.REMAINDER)
    for name in ("status", "logs", "stop"):
        j = jsub.add_parser(name)
        j.add_argument("--address", required=True)
        j.add_argument("job_id")
    j = jsub.add_parser("list")
    j.add_argument("--address", required=True)
    s.set_defaults(fn=cmd_job)

    s = sub.add_parser("serve", help="model serving (deploy/status/shutdown)")
    ssub = s.add_subparsers(dest="serve_cmd", required=True)
    v = ssub.add_parser("deploy")
    v.add_argument("--address", required=True)
    v.add_argument("config_file")
    v = ssub.add_parser("run", help="deploy an import path (module:app)")
    v.add_argument("--address", required=True)
    v.add_argument("--route-prefix", default=None)
    v.add_argument("--blocking", action="store_true")
    v.add_argument("import_path")
    for name in ("status", "shutdown"):
        v = ssub.add_parser(name)
        v.add_argument("--address", required=True)
    s.set_defaults(fn=cmd_serve)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
