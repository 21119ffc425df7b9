"""Tier-1 guard for the invariant analysis plane (tools/rtlint +
the dynamic lock witness).

Three layers:

* seeded-violation fixtures — a tiny synthetic repo per pass with one
  deliberate violation, proving each checker actually FIRES (a linter
  that silently stops matching is worse than none);
* the clean-tree gate — the real repo must lint to zero non-baselined
  findings, which is what makes every invariant in docs/INVARIANTS.md
  a CI property rather than prose;
* baseline semantics — suppressions match on (id, path, substring),
  round-trip through TOML, and stale entries are themselves findings.

The lock witness (ray_tpu/_private/lockwitness.py) is exercised with a
real opposite-order acquisition across two threads; its global state
is reset afterwards so the session-wide no-cycles gate in conftest
stays meaningful.
"""

from __future__ import annotations

import os
import textwrap
import threading

import pytest

from tools.rtlint import BASELINE_PATH, run_lint
from tools.rtlint.core import Baseline, Finding, run_passes
from tools.rtlint.passes import (ALL_PASSES, ClocksPass, FrameBudgetPass,
                                 KnobsPass, LocksPass, MetricsPass,
                                 WirePass)


def seed(tmp_path, files: "dict[str, str]") -> str:
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text), encoding="utf-8")
    return str(tmp_path)


def lint(root: str, pass_cls) -> "list[Finding]":
    active, _counts, _sup = run_passes(root, [pass_cls()], Baseline())
    return active


def ids(findings) -> "set[str]":
    return {f.id for f in findings}


# ---------------------------------------------------------------------------
# RT-W: wire protocol


def test_wire_orphan_kind_names_callsite(tmp_path):
    """A typo'd/half-removed kind is reported with the exact sending
    callsite — path, line, and the kind itself."""
    root = seed(tmp_path, {"ray_tpu/sender.py": '''
        class Plane:
            def ok(self, conn, kind):
                conn.cast("real_kind", {})
                if kind == "real_kind":
                    pass

            def bad(self, conn):
                conn.cast("ghost_kind", {})
        '''})
    found = [f for f in lint(root, WirePass) if f.id == "RT-W001"]
    assert len(found) == 1
    f = found[0]
    assert "ghost_kind" in f.message
    assert f.path == "ray_tpu/sender.py"
    assert f.line == 9  # the conn.cast("ghost_kind", ...) line
    assert "Plane.bad" in f.symbol


def test_wire_non_kind_cast_apis_ignored(tmp_path):
    """memoryview.cast("B") wears the same method name; not a kind."""
    root = seed(tmp_path, {"ray_tpu/buf.py": '''
        def view(buf):
            return memoryview(buf).cast("B")
        '''})
    assert lint(root, WirePass) == []


def test_wire_kind_codes_cross_checks(tmp_path):
    """KIND_CODES entries need senders and receivers; hot kinds need
    codes."""
    root = seed(tmp_path, {
        "ray_tpu/_private/wirefmt.py": '''
            KIND_CODES = {"dead_kind": 1}
            ''',
        "ray_tpu/node.py": '''
            def handle(self, kind):
                if kind == "other":
                    pass
            ''',
    })
    found = lint(root, WirePass)
    assert "RT-W003" in ids(found)  # dead_kind never sent
    assert "RT-W004" in ids(found)  # dead_kind never received
    # seeded KIND_CODES lacks every hot kind -> the pickle-fallback
    # check fires
    assert "RT-W002" in ids(found)


def test_wire_native_enum_cross_check(tmp_path):
    """RT-W005 catches every direction of KIND_CODES <-> rt_kind skew:
    a code value mismatch, a wirefmt kind the C enum lacks, and a C
    enum entry wirefmt lacks (incl. the CAST_BATCH <-> __cast_batch__
    dunder mapping)."""
    root = seed(tmp_path, {
        "ray_tpu/_private/wirefmt.py": '''
            KIND_CODES = {"direct_push": 1, "owner_sealed": 4}
            ''',
        "src/eventloop/eventloop.c": '''
            enum rt_kind {
                RT_KIND_DIRECT_PUSH = 2,
                RT_KIND_CAST_BATCH = 11,
            };
            #define RT_KIND_MAX 16
            ''',
    })
    found = [f for f in lint(root, WirePass) if f.id == "RT-W005"]
    msgs = "\n".join(f.message for f in found)
    assert len(found) == 3
    assert "misroute" in msgs                      # direct_push 1 vs 2
    assert "'owner_sealed'" in msgs                # missing in C
    assert "'__cast_batch__'" in msgs              # missing in wirefmt
    # the skewed-value finding anchors at the C enum line
    assert any(f.path == "src/eventloop/eventloop.c" for f in found)


def test_wire_native_enum_in_sync_is_clean(tmp_path):
    """Matching tables produce no RT-W005 noise."""
    root = seed(tmp_path, {
        "ray_tpu/_private/wirefmt.py": '''
            KIND_CODES = {"direct_push": 1, "__cast_batch__": 11}
            ''',
        "src/eventloop/eventloop.c": '''
            enum rt_kind {
                RT_KIND_DIRECT_PUSH = 1,
                RT_KIND_CAST_BATCH = 11,
            };
            ''',
    })
    assert "RT-W005" not in ids(lint(root, WirePass))


# ---------------------------------------------------------------------------
# RT-K: config knobs


def test_knobs_undeclared_and_dynamic(tmp_path):
    root = seed(tmp_path, {
        "ray_tpu/_private/config.py": '''
            ENV_KNOBS = {"RAY_TPU_DECLARED": ("internal", "fixture")}
            ''',
        "ray_tpu/mod.py": '''
            import os

            def f(name):
                os.environ.get("RAY_TPU_DECLARED")
                os.environ.get("RAY_TPU_BOGUS_KNOB")
                os.environ.get(f"RAY_TPU_{name}")
            ''',
    })
    found = lint(root, KnobsPass)
    k001 = [f for f in found if f.id == "RT-K001"]
    assert len(k001) == 1 and "RAY_TPU_BOGUS_KNOB" in k001[0].message
    assert "RT-K003" in ids(found)  # dynamic composition outside config


def test_knobs_operator_readme_and_stale(tmp_path):
    root = seed(tmp_path, {
        "ray_tpu/_private/config.py": '''
            ENV_KNOBS = {
                "RAY_TPU_TUNE_ME": ("operator", "a knob"),
                "RAY_TPU_NOBODY_READS": ("internal", "stale"),
            }
            ''',
        "ray_tpu/mod.py": '''
            import os

            def f():
                os.environ.get("RAY_TPU_TUNE_ME")
            ''',
        "README.md": "no knob table here\n",
    })
    found = lint(root, KnobsPass)
    k002 = [f for f in found if f.id == "RT-K002"]
    assert len(k002) == 1 and "RAY_TPU_TUNE_ME" in k002[0].message
    k004 = [f for f in found if f.id == "RT-K004"]
    assert len(k004) == 1 and "RAY_TPU_NOBODY_READS" in k004[0].message


def test_knobs_config_field_read_is_declared(tmp_path):
    root = seed(tmp_path, {
        "ray_tpu/_private/config.py": '''
            import dataclasses

            @dataclasses.dataclass
            class Config:
                my_field: int = 3
            ''',
        "ray_tpu/mod.py": '''
            import os

            def f():
                os.environ.get("RAY_TPU_MY_FIELD")
            ''',
    })
    assert lint(root, KnobsPass) == []


# ---------------------------------------------------------------------------
# RT-L: lock discipline


def test_locks_bare_acquire_release(tmp_path):
    root = seed(tmp_path, {"ray_tpu/locky.py": '''
        import threading

        class T:
            def __init__(self):
                self._mu = threading.Lock()

            def bad(self):
                self._mu.acquire()
                do_work()
                self._mu.release()

            def good(self):
                self._mu.acquire()
                try:
                    do_work()
                finally:
                    self._mu.release()
        '''})
    found = [f for f in lint(root, LocksPass) if f.id == "RT-L001"]
    # bad(): the bare acquire AND the non-finally release both flag
    assert len(found) == 2
    assert all("T.bad" == f.symbol for f in found)


def test_locks_blocking_under_lock(tmp_path):
    root = seed(tmp_path, {"ray_tpu/locky.py": '''
        import threading
        import time

        class T:
            def __init__(self):
                self._mu = threading.Lock()
                self.conn = None

            def bad(self):
                with self._mu:
                    time.sleep(1.0)
                    self.conn.call("ping", {})

            def fine(self):
                with self._mu:
                    def later():
                        time.sleep(1.0)
                    return later
        '''})
    found = [f for f in lint(root, LocksPass) if f.id == "RT-L002"]
    assert len(found) == 2  # sleep + conn.call; the closure is exempt
    assert {"T.bad"} == {f.symbol for f in found}


def test_locks_order_cycle(tmp_path):
    root = seed(tmp_path, {"ray_tpu/locky.py": '''
        import threading

        class T:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def other(self):
                with self._b:
                    with self._a:
                        pass
        '''})
    found = [f for f in lint(root, LocksPass) if f.id == "RT-L003"]
    assert len(found) == 1
    assert "_a" in found[0].message and "_b" in found[0].message


def test_locks_call_expansion_edge(tmp_path):
    """with A held, calling a method that takes B is an A->B edge."""
    root = seed(tmp_path, {"ray_tpu/locky.py": '''
        import threading

        class T:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def outer(self):
                with self._a:
                    self.inner()

            def inner(self):
                with self._b:
                    pass

            def backwards(self):
                with self._b:
                    with self._a:
                        pass
        '''})
    found = [f for f in lint(root, LocksPass) if f.id == "RT-L003"]
    assert len(found) == 1


# ---------------------------------------------------------------------------
# RT-C: clock discipline


def test_clocks_elapsed_on_wall(tmp_path):
    root = seed(tmp_path, {"ray_tpu/clocky.py": '''
        import time

        def elapsed_bad():
            t0 = time.time()
            work()
            return time.time() - t0

        def elapsed_good():
            t0 = time.monotonic()
            work()
            return time.monotonic() - t0

        def deadline_fine(timeout):
            deadline = time.time() + timeout
            return deadline - time.time()
        '''})
    found = lint(root, ClocksPass)
    assert len(found) == 1 and found[0].id == "RT-C001"
    assert found[0].symbol == "elapsed_bad"


def test_clocks_resolves_time_module_aliases(tmp_path):
    """import time as _t must not hide wall-clock arithmetic (the
    node_agent heartbeat loop imports time aliased)."""
    root = seed(tmp_path, {"ray_tpu/clocky.py": '''
        import time as _t

        def elapsed_bad():
            t0 = _t.time()
            work()
            return _t.time() - t0
        '''})
    found = lint(root, ClocksPass)
    assert len(found) == 1 and found[0].id == "RT-C001"


def test_clocks_mixed_operands(tmp_path):
    root = seed(tmp_path, {"ray_tpu/clocky.py": '''
        import time

        def mixed():
            t0 = time.monotonic()
            return time.time() - t0
        '''})
    found = lint(root, ClocksPass)
    assert len(found) == 1 and found[0].id == "RT-C002"


# ---------------------------------------------------------------------------
# RT-M: metrics


def test_metrics_undocumented_series_and_label(tmp_path):
    root = seed(tmp_path, {"ray_tpu/metricky.py": '''
        def expo(v):
            lines = []
            lines.append("# TYPE ray_tpu_bogus_series gauge")
            lines.append(f'ray_tpu_bogus_series{{task_id="{v}"}} 1')
            return lines
        '''})
    found = lint(root, MetricsPass)
    m001 = [f for f in found if f.id == "RT-M001"]
    assert len(m001) == 1 and "ray_tpu_bogus_series" in m001[0].message
    m002 = [f for f in found if f.id == "RT-M002"]
    assert len(m002) == 1 and "task_id" in m002[0].message


def test_metrics_documented_series_is_clean(tmp_path):
    root = seed(tmp_path, {
        "ray_tpu/metricky.py": '''
            def expo(nid):
                return [f'ray_tpu_known_total{{node_id="{nid}"}} 1']
            ''',
        "docs/OBSERVABILITY.md": "`ray_tpu_known_total` counts things\n",
    })
    assert lint(root, MetricsPass) == []


def test_metrics_prose_mentions_are_not_emissions(tmp_path):
    root = seed(tmp_path, {"ray_tpu/metricky.py": '''
        """Talks about ray_tpu_imaginary_series and shows an example
        call(outs, op="sum") that is not an exposition label."""
        '''})
    assert lint(root, MetricsPass) == []


def test_metrics_alert_rule_consumer_uncatalogued(tmp_path):
    """RT-M003: an alert rule watching a series the catalog doesn't
    document — classic 'rule over a series nothing emits'."""
    root = seed(tmp_path, {
        "ray_tpu/_private/alertplane.py": '''
            def default_rules(config):
                return [{
                    "name": "ghost", "kind": "threshold",
                    "series": "ray_tpu_series_nobody_emits",
                    "agg": "last", "op": ">", "threshold": 1.0,
                }]
            ''',
        "docs/OBSERVABILITY.md": "`ray_tpu_known_total` only\n",
    })
    found = lint(root, MetricsPass)
    assert [f.id for f in found] == ["RT-M003"]
    assert "ray_tpu_series_nobody_emits" in found[0].message
    assert "alert rule" in found[0].message


def test_metrics_query_consumer_uncatalogued(tmp_path):
    """RT-M003 fires on operator-surface range queries too (the CLI /
    dashboard side), in any module."""
    root = seed(tmp_path, {"ray_tpu/scripts.py": '''
        def top(us):
            return us.query_metrics("ray_tpu_phantom_gauge",
                                    start=0.0)
        '''})
    found = lint(root, MetricsPass)
    assert [f.id for f in found] == ["RT-M003"]
    assert "query_metrics() consumer" in found[0].message


def test_metrics_catalogued_consumers_are_clean(tmp_path):
    """Rules and queries over documented series produce nothing; a
    dynamic first argument is never harvested."""
    root = seed(tmp_path, {
        "ray_tpu/_private/alertplane.py": '''
            def default_rules(config):
                return [{
                    "name": "ok", "kind": "burn_rate",
                    "bad": "ray_tpu_bad_total",
                    "total": "ray_tpu_all_total",
                }]
            ''',
        "ray_tpu/scripts.py": '''
            def top(us, name):
                us.query_metrics("ray_tpu_bad_total")
                us.query_metrics(name)  # dynamic: not harvested
            ''',
        "docs/OBSERVABILITY.md":
            "`ray_tpu_bad_total` bad\n`ray_tpu_all_total` all\n",
    })
    assert lint(root, MetricsPass) == []


# ---------------------------------------------------------------------------
# RT-F: head-frame budget


def test_framebudget_transitive_unbuffered_send(tmp_path):
    """An unbuffered head cast two self-calls deep inside a hot-path
    entry is found with the full chain; cast_buffered is exempt."""
    root = seed(tmp_path, {"ray_tpu/_private/direct.py": '''
        class Direct:
            def _push(self, spec):
                self._notify(spec)
                self.rt.conn.cast_buffered("ok_amortized", {})

            def _notify(self, spec):
                self.rt.conn.cast("per_call_frame", {})
        '''})
    found = [f for f in lint(root, FrameBudgetPass)
             if f.id == "RT-F001"]
    assert len(found) == 1
    assert "_push -> _notify" in found[0].message
    assert found[0].symbol == "Direct._notify"


def test_framebudget_dict_get_is_not_an_edge(tmp_path):
    """A non-self .get() must not splice the module's get() into the
    call graph (the false-positive this pass shipped without)."""
    root = seed(tmp_path, {"ray_tpu/_private/runtime.py": '''
        class CoreRuntime:
            def _store_owned_and_notify(self, d):
                d.get("x")

            def get(self, ref):
                self.conn.call("fetch", {})
        '''})
    assert lint(root, FrameBudgetPass) == []


# ---------------------------------------------------------------------------
# clean tree + baseline


def test_repo_tree_is_lint_clean():
    """THE gate: zero non-baselined findings across the shipped tree.
    A new invariant violation anywhere in ray_tpu/ fails here with its
    exact callsite; fix it or (rarely) baseline it with a written
    reason."""
    active, counts, _sup = run_lint()
    assert sorted(counts) == sorted(p.name for p in ALL_PASSES)
    assert active == [], "\n".join(f.render() for f in active)


def test_shipped_baseline_loads_and_is_live():
    """Every shipped suppression must still match something (RT-X002
    otherwise, covered by the clean-tree gate); spot-check the loader
    on the real file."""
    b = Baseline.load(BASELINE_PATH)
    for e in b.entries:
        assert e["id"] and e["path"] and e["reason"]


def test_baseline_roundtrip(tmp_path):
    f1 = Finding("RT-L002", "ray_tpu/_private/gcs.py", 41,
                 "blocking op .sleep() inside 'with self._mu:'",
                 "Gcs._h_x")
    f2 = Finding("RT-W001", "ray_tpu/other.py", 7, "kind 'z' unsent")
    path = tmp_path / "baseline.toml"
    path.write_text(Baseline.render([f1], "accepted: fixture"),
                    encoding="utf-8")
    b = Baseline.load(str(path))
    assert b.suppresses(f1)
    # different line, same (id, path, symbol): still suppressed
    assert b.suppresses(Finding(f1.id, f1.path, 999, f1.message,
                                f1.symbol))
    assert not b.suppresses(f2)
    assert b.unused() == []


def test_baseline_stale_entry_is_a_finding(tmp_path):
    path = tmp_path / "baseline.toml"
    path.write_text(textwrap.dedent('''
        [[suppress]]
        id = "RT-W001"
        path = "ray_tpu/nowhere.py"
        reason = "matches nothing"
        '''), encoding="utf-8")
    b = Baseline.load(str(path))
    (tmp_path / "ray_tpu").mkdir()
    active, _c, _s = run_passes(str(tmp_path), [WirePass()], b)
    assert ids(active) == {"RT-X002"}


def test_syntax_error_is_a_finding(tmp_path):
    root = seed(tmp_path, {"ray_tpu/broken.py": "def f(:\n"})
    active, _c, _s = run_passes(root, [], Baseline())
    assert ids(active) == {"RT-X001"}


def test_cli_lint_subcommand_clean():
    """ray-tpu lint on the shipped tree exits 0 (text and json)."""
    from ray_tpu.scripts import main

    assert main(["lint"]) == 0
    assert main(["lint", "--pass", "wire", "--format", "json"]) == 0


# ---------------------------------------------------------------------------
# the dynamic half: lock witness


def _runtime_scoped_locks(n_rlocks: int = 0):
    """Allocate locks whose (compiled) filename sits inside the
    package, so the witness factories wrap them exactly as they wrap
    real runtime locks."""
    import ray_tpu

    fake = os.path.join(os.path.dirname(ray_tpu.__file__),
                        "_witness_fixture.py")
    n = 2
    src = "import threading\n" + "".join(
        f"L{i} = threading.{'RLock' if i < n_rlocks else 'Lock'}()\n"
        for i in range(n))
    g: dict = {}
    exec(compile(src, fake, "exec"), g)
    return g["L0"], g["L1"]


@pytest.fixture
def witness():
    from ray_tpu._private import lockwitness

    lockwitness.install()
    lockwitness.reset()
    yield lockwitness
    # leave installed (conftest armed it session-wide); drop the
    # fixture-made cycles so the session no-cycles gate stays real
    lockwitness.reset()


def test_witness_detects_opposite_order_cycle(witness):
    a, b = _runtime_scoped_locks()
    assert type(a).__name__ == "_WitnessLock"

    with a:
        with b:
            pass

    def reversed_order():
        with b:
            with a:
                pass

    t = threading.Thread(target=reversed_order)
    t.start()
    t.join()

    cycles = witness.cycles()
    assert len(cycles) == 1
    rep = witness.report()
    assert "_witness_fixture.py:2" in rep
    assert "_witness_fixture.py:3" in rep
    assert "stack:" in rep


def test_witness_consistent_order_is_clean(witness):
    a, b = _runtime_scoped_locks()
    for _ in range(3):
        with a:
            with b:
                pass
    assert witness.cycles() == []
    assert witness.edge_count() == 1


def test_witness_condition_wait_releases_held_stack(witness):
    a, _ = _runtime_scoped_locks(n_rlocks=1)
    assert type(a).__name__ == "_WitnessRLock"
    cv = threading.Condition(a)
    hit = []

    def waker():
        with cv:
            hit.append(True)
            cv.notify()

    with cv:
        t = threading.Thread(target=waker)
        t.start()
        # wait() releases the wrapped RLock via _release_save; if the
        # witness still thought it held, the waker's acquire would
        # record edges from a lock that is not actually held
        assert cv.wait(timeout=5)
    t.join()
    assert hit and witness.cycles() == []


def test_witness_ignores_foreign_locks(witness):
    # allocated from THIS file (tests/) -> wrapped; from a tempfile
    # path outside the package markers -> untouched
    src = "import threading\nL = threading.Lock()\n"
    g: dict = {}
    exec(compile(src, "/somewhere/else/app.py", "exec"), g)
    assert type(g["L"]) is not type(_runtime_scoped_locks()[0])
    assert g["L"].__class__.__module__ == "_thread"
