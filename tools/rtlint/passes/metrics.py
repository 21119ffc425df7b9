"""RT-M: metrics cross-check.

Every ``ray_tpu_*`` Prometheus series the runtime exposes is an
operator contract: dashboards, alerts, and the Grafana bundle
(``util/metrics_export.py``) are built against the names, and an
undocumented series is one nobody alerts on. Labels are the sharper
edge: a label whose values are unbounded (task ids, object ids, trace
ids) makes the time-series database's cardinality explode — the
classic self-inflicted monitoring outage.

Checks:
  RT-M001  series emitted in code but absent from
           docs/OBSERVABILITY.md (the metric catalog operators read)
  RT-M002  exposition label key outside the bounded-cardinality
           registry below — either add it here with a written
           cardinality argument (as a pass change, reviewed), or drop
           the label
  RT-M003  series CONSUMED by the alerting/operator plane — referenced
           in an alert-rule dict (``alertplane.py`` ``series``/``bad``/
           ``total`` values) or range-queried by an operator surface
           (a ``query_metrics("...")`` call, e.g. ``ray-tpu top``) —
           that the OBSERVABILITY.md catalog doesn't document. An
           alert over an uncatalogued series is one an operator cannot
           look up at 3am; usually it means the rule watches a series
           nothing emits.

Series are harvested from EMISSION contexts only, because plenty of
non-metric strings start with ``ray_tpu_`` (thread names, contextvar
names, option keys, KV keys). A name counts as a series when it is:

  * the first argument of a ``Gauge``/``Counter``/``Histogram``/
    ``Summary`` constructor call;
  * the token after ``# TYPE`` in an exposition string;
  * a string/f-string constant where the name is followed by ``{``
    (label block) or, at line start, by a space (bare exposition
    line) — the shapes ``runtime_stats_text`` renders;
  * followed by ``[`` (a PromQL range selector);
  * any mention inside ``util/metrics_export.py`` — the Grafana
    bundle is all PromQL, and a dashboard panel over a series the
    catalog doesn't document is exactly the drift this pass exists
    to catch.

Wildcard mentions (``ray_tpu_serve_*`` in prose) and dynamic
compositions (the user-metric prefixer ``f"ray_tpu_{name}_total"`` —
user series are the user's catalog) never match these shapes.
Histogram suffixes (``_bucket``/``_sum``/``_count``) fold into their
family name.
"""

from __future__ import annotations

import ast
import re

from tools.rtlint.core import Finding, RepoTree, const_str, \
    enclosing_symbols

DOCS = "docs/OBSERVABILITY.md"

# Every ray_tpu_* token in this module is a PromQL/dashboard mention.
DASHBOARD_MODULE = "ray_tpu/util/metrics_export.py"

# Alert-rule registry module: dict values under these keys name the
# series the in-cluster SLO engine evaluates (RT-M003 consumers).
ALERT_MODULE = "ray_tpu/_private/alertplane.py"
_RULE_SERIES_KEYS = {"series", "bad", "total"}

# Label keys with a bounded value set, and why they are bounded:
#   node_id/node/peer/target — cluster nodes / connections, lease-
#                bounded (hundreds at most)
#   reason     — death/shed classification enums
#   phase/where/path/direction/kind — fixed enum-like path names
#   le/quantile— histogram bucket bounds (fixed list)
#   deployment/model/pool — operator-declared serving surfaces
#   callsite   — interned + folded past object_census_report_groups
#   job        — live jobs, bounded by admission control
#   trace_id/name — ray_tpu_trace_exemplar_info only: the head's
#                trace table is hard-bounded (trace_table_max=512,
#                exemplar retention keeps a fixed-size working set)
#   state      — object lifecycle states (fixed enum in object store)
#   role       — profiling-plane process roles (fixed enum: head /
#                agent / worker / driver)
#   frame      — ray_tpu_profile_self_hits only: the head folds
#                self-time to a fixed top-N per role before exposition,
#                so cardinality is N*roles regardless of code shape
#   severity   — alert-plane severity: fixed enum (page/warn/info,
#                alertplane.SEVERITIES), every value pre-registered in
#                the exposition so cardinality is exactly 3
ALLOWED_LABELS = {
    "node_id", "node", "reason", "phase", "where", "le", "deployment",
    "model", "pool", "callsite", "peer", "job", "kind", "quantile",
    "trace_id", "name", "direction", "path", "target", "state",
    "role", "frame", "severity",
}

_METRIC_CTORS = {"Gauge", "Counter", "Histogram", "Summary"}

_SERIES_RE = re.compile(r"ray_tpu_[a-z0-9_]*[a-z0-9]")
_TYPE_RE = re.compile(r"#\s*TYPE\s+(ray_tpu_[a-z0-9_]*[a-z0-9])")
_LABEL_RE = re.compile(r'[{,]\s*([a-zA-Z_][a-zA-Z0-9_]*)\s*=\s*"')
_HIST_SUFFIX = re.compile(r"_(bucket|sum|count)$")


def _doc_series(text: str) -> "set[str]":
    return set(_SERIES_RE.findall(text))


def _emitted_in(text: str, harvest_all: bool) -> "list[str]":
    """Series names this string actually emits/queries (see module
    docstring for the shapes)."""
    out = [m for m in _TYPE_RE.findall(text)]
    for m in _SERIES_RE.finditer(text):
        end = m.end()
        nxt = text[end] if end < len(text) else ""
        line_start = m.start() == 0 or text[m.start() - 1] == "\n"
        if (harvest_all and nxt not in "*_"):
            out.append(m.group())
        elif nxt == "{" or nxt == "[" or (nxt == " " and line_start):
            out.append(m.group())
    return out


class MetricsPass:
    name = "metrics"
    id_prefix = "RT-M"

    def run(self, tree: RepoTree) -> "list[Finding]":
        documented = _doc_series(tree.doc_text(DOCS))
        out: list[Finding] = []
        seen_series: set[str] = set()
        seen_labels: set[str] = set()

        def flag_series(series, mod, lineno, sym):
            series = _HIST_SUFFIX.sub("", series)
            if series in documented or series in seen_series:
                return
            seen_series.add(series)
            out.append(Finding(
                "RT-M001", mod.relpath, lineno,
                f"metric series {series!r} is emitted here but not "
                f"documented in {DOCS}", sym))

        def flag_consumer(series, mod, lineno, sym, what):
            series = _HIST_SUFFIX.sub("", series)
            if series in documented or (series, "m3") in seen_series:
                return
            seen_series.add((series, "m3"))
            out.append(Finding(
                "RT-M003", mod.relpath, lineno,
                f"{what} reads series {series!r} but {DOCS} does not "
                f"catalog it — either it is emitted-but-undocumented "
                f"or the consumer watches a series nothing emits", sym))

        for mod in tree.modules:
            harvest_all = mod.relpath == DASHBOARD_MODULE
            syms = None
            # RT-M003 consumer side (a): alert-rule dict values.
            if mod.relpath == ALERT_MODULE:
                syms = enclosing_symbols(mod.tree)
                for node in ast.walk(mod.tree):
                    if not isinstance(node, ast.Dict):
                        continue
                    for k, v in zip(node.keys, node.values):
                        key = const_str(k) if k is not None else None
                        val = const_str(v)
                        if key in _RULE_SERIES_KEYS and val \
                                and _SERIES_RE.fullmatch(val):
                            flag_consumer(val, mod, v.lineno,
                                          syms.get(v.lineno, ""),
                                          "alert rule")
            # RT-M003 consumer side (b): operator-surface range queries
            # (ray-tpu top / metrics CLI, dashboard endpoints).
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                fn = node.func
                fname = fn.attr if isinstance(fn, ast.Attribute) \
                    else fn.id if isinstance(fn, ast.Name) else ""
                if fname != "query_metrics":
                    continue
                s = const_str(node.args[0])
                if s and _SERIES_RE.fullmatch(s):
                    if syms is None:
                        syms = enclosing_symbols(mod.tree)
                    flag_consumer(s, mod, node.lineno,
                                  syms.get(node.lineno, ""),
                                  "query_metrics() consumer")
            # f-string constant parts are re-examined as a whole
            # below (split exposition strings like
            # f'ray_tpu_x' f'{{node="{n}"}}'); skip them standalone.
            in_fstring = {
                id(v) for js in ast.walk(mod.tree)
                if isinstance(js, ast.JoinedStr) for v in js.values}
            for node in ast.walk(mod.tree):
                # metric-object constructors: Gauge("ray_tpu_x", ...)
                if (isinstance(node, ast.Call) and node.args):
                    fn = node.func
                    ctor = fn.attr if isinstance(fn, ast.Attribute) \
                        else fn.id if isinstance(fn, ast.Name) else ""
                    s = const_str(node.args[0])
                    if ctor in _METRIC_CTORS and s \
                            and _SERIES_RE.fullmatch(s):
                        if syms is None:
                            syms = enclosing_symbols(mod.tree)
                        flag_series(s, mod, node.lineno,
                                    syms.get(node.lineno, ""))
                if isinstance(node, ast.JoinedStr):
                    # interpolations become \x00 so a dynamic series
                    # (f"ray_tpu_{name}_total") can never match
                    text = "".join(
                        v.value if (isinstance(v, ast.Constant)
                                    and isinstance(v.value, str))
                        else "\x00" for v in node.values)
                elif (isinstance(node, ast.Constant)
                        and isinstance(node.value, str)
                        and id(node) not in in_fstring):
                    text = node.value
                else:
                    continue
                if "ray_tpu_" not in text:
                    continue
                if syms is None:
                    syms = enclosing_symbols(mod.tree)
                sym = syms.get(node.lineno, "")
                emitted = _emitted_in(text, harvest_all)
                for series in emitted:
                    flag_series(series, mod, node.lineno, sym)
                if not emitted:
                    # prose mention (docstring), not an exposition or
                    # query string — kwargs like op="sum" in examples
                    # are not labels
                    continue
                for lm in _LABEL_RE.finditer(text):
                    label = lm.group(1)
                    if label in ALLOWED_LABELS or label in seen_labels:
                        continue
                    seen_labels.add(label)
                    out.append(Finding(
                        "RT-M002", mod.relpath, node.lineno,
                        f"exposition label {label!r} is not in the "
                        f"bounded-cardinality registry — unbounded "
                        f"label values melt the TSDB", sym))
        return out
