"""Who ends a worker process, and where a chip lease begins.

``worker_exit.end_workers`` is the one place a worker process is ended:
it returns only when the process has been waited for, however long the
kernel takes to let go of its chips. ``ray_tpu.shutdown()`` therefore
leaves no process of the session behind, and ``Worker._hold_chips``
starts a lease only where the last holder's device nodes can be opened.

Nothing here touches libtpu: stand-in process handles, fake chips
(``num_tpus=4``) and a patched ``os.open``. Every wait carries its own
deadline, so a fault fails a test and cannot hang the tier.
"""

from __future__ import annotations

import errno
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from ray_tpu._private import rpc, worker_exit
from ray_tpu._private.worker import Worker


class StandInProc:
    """Popen-shaped. Goes ``dies_after`` s after it is told to (by the
    cast or SIGTERM if ``heeds_request``, else only by SIGKILL); until
    then ``wait`` keeps timing out."""

    pid = 2 ** 22 + 1  # above pid_max's default: /proc has none

    def __init__(self, dies_after: float, heeds_request: bool = True):
        self.dies_after = dies_after
        self.heeds_request = heeds_request
        self.calls: list[tuple[str, float]] = []
        self.gone_at: float | None = None
        self.returncode = None
        self.t0 = time.monotonic()

    def _told(self, how: str, heeded: bool, rc: int) -> None:
        self.calls.append((how, time.monotonic() - self.t0))
        if heeded and self.gone_at is None:
            self.gone_at = time.monotonic() + self.dies_after
            self._rc = rc

    def cast(self, kind, body):  # its own connection, for brevity
        assert kind == "kill"
        self._told("cast", self.heeds_request, 0)

    def terminate(self):
        self._told("terminate", self.heeds_request, -15)

    def kill(self):
        self._told("kill", True, -9)

    def poll(self):
        if self.gone_at is not None and time.monotonic() >= self.gone_at:
            self.returncode = self._rc
        return self.returncode

    def wait(self, timeout=None):
        assert timeout is not None, "a wait without a deadline"
        deadline = time.monotonic() + timeout
        while self.poll() is None:
            if time.monotonic() >= deadline:
                raise subprocess.TimeoutExpired("stand-in", timeout)
            time.sleep(0.01)
        return self.returncode

    def sent(self, how: str) -> list[float]:
        return [t for h, t in self.calls if h == how]


def test_a_chip_holder_is_waited_for_where_a_chipless_worker_is_killed():
    """Both take 3.5 s to go, longer than the 3 s after which the old
    shutdown() left a process behind. The one that may hold chips is
    waited for and never SIGKILLed; the chipless one is SIGKILLed, once,
    when its 2 s are over, and then waited for until it is gone."""
    holder = StandInProc(3.5)
    chipless = StandInProc(1.5, heeds_request=False)
    t0 = time.monotonic()
    seen_c, seen_h = worker_exit.end_workers(
        [(chipless, chipless, False), (holder, holder, True)])
    took = time.monotonic() - t0
    assert holder.poll() == 0 and chipless.poll() == -9  # both reaped
    assert 3.5 <= took < 6.0
    assert seen_h == worker_exit.WorkerExit(0, None, seen_h.seconds,
                                            False, True)
    assert 3.5 <= seen_h.seconds <= took and not holder.sent("kill")
    assert seen_c.escalated and seen_c.gone and seen_c.term_signal == 9
    assert seen_c.exit_code is None and seen_c.seconds >= 3.5
    assert len(chipless.sent("kill")) == 1
    # Both were told before either was waited for.
    assert holder.sent("cast")[0] < 0.1 and chipless.sent("cast")[0] < 0.1
    assert 2.0 <= chipless.sent("kill")[0] < 3.0


@pytest.mark.parametrize("tpu_capable, grace", [
    (False, "CHIPLESS_GRACE_S"), (True, "CHIP_HOLDER_GRACE_S")])
def test_escalates_once_and_only_after_the_grace(monkeypatch, tpu_capable,
                                                 grace):
    """Two hung workers (they heed no request): each is SIGKILLed once,
    after the grace its kind gets, which the workers of one call share
    (both were told at the start), and each is then reaped."""
    monkeypatch.setattr(worker_exit, grace, 1.0)
    procs = [StandInProc(0.2, heeds_request=False) for _ in range(2)]
    exits = worker_exit.end_workers([(p, None, tpu_capable) for p in procs])
    for p, seen in zip(procs, exits):
        assert len(p.sent("terminate")) == 1 and len(p.sent("kill")) == 1
        assert p.sent("kill")[0] >= 1.0
        assert seen.escalated and seen.gone and seen.term_signal == 9
    assert exits[1].seconds < 2.0  # 1.0 + 0.2 + 0.2, not 2 x (1.0 + 0.2)


def test_past_the_bound_it_says_so_on_stderr(monkeypatch, capfd):
    monkeypatch.setattr(worker_exit, "CHIP_HOLDER_GRACE_S", 0.2)
    monkeypatch.setattr(worker_exit, "CHIP_RELEASE_BOUND_S", 0.6)
    stuck = StandInProc(30.0)
    (seen,) = worker_exit.end_workers([(stuck, None, True)])
    assert not seen.gone and seen.escalated and 0.6 <= seen.seconds < 2.0
    assert seen.exit_code is None and seen.term_signal is None
    assert len(stuck.sent("kill")) == 1
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if f"pid {stuck.pid}" in ln]
    assert len(lines) == 1, lines
    assert "state unknown" in lines[0] and "chips are not free" in lines[0]


def test_an_exited_worker_is_only_reaped_and_a_remote_one_only_told():
    class Conn:
        casts = 0

        def cast(self, kind, body):
            Conn.casts += 1
            raise rpc.ConnectionLost("gone")

    dead = subprocess.Popen([sys.executable, "-c", "raise SystemExit(3)"])
    deadline = time.monotonic() + 30
    while worker_exit._proc_stat(dead.pid)[0] != "Z":  # exited, not reaped
        assert time.monotonic() < deadline
        time.sleep(0.01)
    seen, remote = worker_exit.end_workers(
        [(dead, Conn(), False), (None, Conn(), True)])
    assert (seen.exit_code, seen.term_signal, seen.escalated, seen.gone) \
        == (3, None, False, True)
    assert remote is None and Conn.casts == 1  # a lost connection is no error


def test_pid_handle_sees_a_reaped_child_and_an_orphaned_zombie_as_gone():
    """A zygote child is waited for by pid. It is gone when its parent
    has reaped it; a zombie whose parent is not the one it was forked by
    will never be reaped by anyone of ours, and has exited all the same."""
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        handle = worker_exit.PidHandle(child.pid)
        assert handle.poll() is None
        with pytest.raises(subprocess.TimeoutExpired):
            handle.wait(timeout=0.1)
        handle.kill()
        deadline = time.monotonic() + 10
        while worker_exit._proc_stat(child.pid)[0] != "Z":
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert handle.poll() is None  # a zombie its parent can still reap
        handle._ppid = -1             # ... and one whose parent is gone
        assert handle.wait(timeout=5) == 0
        handle._ppid = os.getpid()
        child.wait(timeout=10)
        assert handle.wait(timeout=5) == 0 and handle.returncode is None
    finally:
        child.kill()
        child.wait(timeout=10)


# --- the postcondition of shutdown() --------------------------------------

SESSION = textwrap.dedent("""
    import json, os, sys, time
    import ray_tpu
    from ray_tpu._private.worker_context import get_head
    from ray_tpu._private.worker_exit import PidHandle, _proc_stat

    order = sys.argv[1]
    ray_tpu.init(num_cpus=4, num_tpus=4, object_store_memory=32 * 1024 * 1024)
    head = get_head()
    if order == "at_once":  # the zygote is still warming up: it goes too
        head._zygote().start_async()
        session_dir = head.session_dir
        ray_tpu.shutdown()
        time.sleep(0.5)  # a zygote left behind would be importing, or up, by now
        print(json.dumps({"left": [e for e in os.listdir("/proc") if e.isdigit()
                                   and int(e) != os.getpid() and _proc_stat(int(e))
                                   and (_proc_stat(int(e))[1] == os.getpid())]}))
        sys.exit(0)

    @ray_tpu.remote
    class Pid:
        def pid(self):
            return os.getpid()

    # Fork the chipless workers from the zygote, as a warm session does.
    head._zygote().start_async()
    assert head._zygote()._ready.wait(60), "the zygote never warmed up"
    actors = [Pid.remote() for _ in range(3)]
    holder = Pid.options(num_tpus=4).remote()  # a fresh, chip-capable process
    pids = ray_tpu.get([a.pid.remote() for a in actors + [holder]], timeout=60)
    with head.lock:
        forked = sum(isinstance(r.proc, PidHandle) for r in head.workers.values())
        capable = [r.pid for r in head.workers.values() if r.tpu_capable]
    zygote_pid = head._zygote()._proc.pid
    session_dir = head.session_dir
    t0 = time.monotonic()
    if order == "kill_first":
        ray_tpu.kill(holder)  # the train job's order: kill, then shutdown at once
    ray_tpu.shutdown()
    took = time.monotonic() - t0

    left = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        pid, st = int(entry), _proc_stat(int(entry))
        if st is None:
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                ours = session_dir.encode() in f.read()
        except OSError:
            ours = False
        if st[1] == os.getpid() or ours or pid in pids + [zygote_pid]:
            left[pid] = st
    print(json.dumps({"left": left, "forked": forked, "capable": capable,
                      "holder": pids[-1], "took": took}))
""")


@pytest.mark.parametrize("order", ["shutdown", "kill_first", "at_once"])
def test_shutdown_leaves_no_process_of_the_session(tmp_path, order):
    """Zygote-forked workers and one fake chip holder: when shutdown()
    returns, no child of the driver, no process carrying the session's
    directory and none of the pids the session reported is alive or a
    zombie, also when ray_tpu.kill() of the holder comes just before,
    and when shutdown() comes before the zygote has warmed up."""
    script = tmp_path / "session.py"
    script.write_text(SESSION)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p]))
    run = subprocess.run([sys.executable, str(script), order], env=env,
                         capture_output=True, text=True, timeout=240)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-4000:]
    seen = json.loads(run.stdout.strip().splitlines()[-1])
    if order == "at_once":
        assert seen["left"] == [], seen
        return
    assert seen["forked"] >= 3 and seen["capable"] == [seen["holder"]], seen
    assert seen["left"] == {}, seen
    assert seen["took"] < 30, seen


# --- a chip lease begins where the last holder has let go ------------------

class _LeaseWorker:
    """What _hold_chips touches of a Worker."""

    worker_id = "worker-test"
    _chips = None
    _hold_chips = Worker._hold_chips


@pytest.fixture
def lease(monkeypatch):
    """Two fake device nodes. Opening one raises what ``errors`` holds
    for it, one entry an open, and then succeeds."""
    nodes = ["/dev/vfio/0", "/dev/vfio/1"]
    opened, errors = [], {n: [] for n in nodes}
    real_open = os.open

    def fake_open(path, flags, *a, **kw):
        if path not in nodes:
            return real_open(path, flags, *a, **kw)
        opened.append(path)
        if errors[path]:
            err = errors[path].pop(0)
            raise OSError(err, os.strerror(err), path)
        return real_open(os.devnull, os.O_RDWR)

    from ray_tpu.accelerators import tpu

    monkeypatch.setattr(tpu, "host_chip_nodes", lambda: list(nodes))
    monkeypatch.setattr(os, "open", fake_open)
    for k in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
              "TPU_PROCESS_BOUNDS"):
        monkeypatch.setenv(k, "")  # restored after the test
    return nodes, opened, errors


def test_a_lease_waits_for_the_last_holder(lease, capfd):
    nodes, opened, errors = lease
    errors[nodes[0]] += [errno.EBUSY, errno.EBUSY]  # then it opens
    w = _LeaseWorker()
    t0 = time.monotonic()
    w._hold_chips([0, 1])
    assert 0.2 <= time.monotonic() - t0 < 5
    assert w._chips == [0, 1] and os.environ["TPU_VISIBLE_CHIPS"] == "0,1"
    assert opened == [nodes[0], nodes[1], nodes[0], nodes[0]]
    lines = [ln for ln in capfd.readouterr().err.splitlines() if "waited" in ln]
    assert len(lines) == 1 and "/dev/vfio/0" in lines[0], lines
    w._hold_chips([0, 1])  # every push repeats the lease: no second probe
    assert len(opened) == 4


def test_a_lease_that_cannot_begin_names_the_busy_nodes(lease, monkeypatch):
    nodes, opened, errors = lease
    monkeypatch.setattr(worker_exit, "CHIP_RELEASE_BOUND_S", 0.3)
    errors[nodes[0]] += [errno.EACCES]
    errors[nodes[1]] += [errno.EBUSY] * 1000  # never frees
    w = _LeaseWorker()
    with pytest.raises(RuntimeError, match="/dev/vfio/1 still busy") as e:
        w._hold_chips([0, 1])
    assert "/dev/vfio/0" not in str(e.value)  # "cannot tell" is not "busy"
    assert w._chips is None  # no lease was taken


@pytest.mark.parametrize("nodes, chips, probed", [
    ([], [0, 1, 2, 3], []),
    (["/dev/vfio/0", "/dev/vfio/1"], [1], ["/dev/vfio/1"]),
    (["/dev/vfio/0", "/dev/vfio/1"], [0, 1, 2, 3], ["/dev/vfio/0", "/dev/vfio/1"])])
def test_a_lease_probes_its_own_nodes_and_nothing_without_device_files(
        lease, monkeypatch, capfd, nodes, chips, probed):
    """A host with no device files (every CPU test) probes nothing. A
    lease of part of a host probes the nodes of its own chips alone (a
    sibling of the same session holds the others for good), and a fake
    ``num_tpus`` larger than the host only the nodes there are."""
    from ray_tpu.accelerators import tpu

    _, opened, _ = lease
    monkeypatch.setattr(tpu, "host_chip_nodes", lambda: list(nodes))
    w = _LeaseWorker()
    w._hold_chips(chips)
    assert w._chips == chips and opened == probed
    assert "waited" not in capfd.readouterr().err
