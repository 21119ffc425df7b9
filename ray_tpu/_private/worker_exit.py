"""Who ends a worker process, and where a chip lease begins.

On a TPU the end of a process is what gives its chips back: libtpu opens
the host's device nodes exclusively and the kernel closes them seconds
after SIGKILL for a holder of gigabytes of device memory. So the end of
a worker has one owner (``end_workers``: every kill, retirement,
reconnect and shutdown calls it, and it returns only when the process
has been waited for), and a chip lease starts by seeing that the last
holder has let go (``await_chips_free``, from ``Worker._hold_chips``).
"""

from __future__ import annotations

import errno
import os
import subprocess
import sys
import time
from typing import NamedTuple

from ray_tpu._private import rpc

# The one bound both halves share: how long a holder of chips may take
# to let go. Five times the longest release on record (12.5 s, four
# chips at 7.4 GB each; PERF.md section 6, PR 46).
CHIP_RELEASE_BOUND_S = 60.0
# What a worker gets to leave by itself before SIGKILL: chipless workers
# share one budget a call; a chip holder gets what its own exit backstop
# gives libtpu's teardown (Worker._on_message "exit_worker").
CHIPLESS_GRACE_S = 2.0
CHIP_HOLDER_GRACE_S = 10.0


def _proc_stat(pid: int) -> "tuple[str, int] | None":
    """(state, parent pid) of /proc/<pid>/stat; None if there is none."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            state, ppid = f.read().rsplit(")", 1)[1].split()[:2]
        return state, int(ppid)
    except (OSError, IndexError, ValueError):
        return None


class PidHandle:
    """Popen-shaped handle for a worker forked by a zygote. The zygote
    is the OS parent: it reaps the child and keeps its wait status
    (``ZygoteClient.exit_status``), so this handle can only signal and
    see whether the process is still there."""

    returncode = None

    def __init__(self, pid: int):
        self.pid = pid
        stat = _proc_stat(pid)
        self._ppid = stat[1] if stat else None

    def poll(self):
        try:
            os.kill(self.pid, 0)
        except ProcessLookupError:
            return 0
        except OSError:
            pass
        # A zombie whose zygote died before it has no one left to reap
        # it: it has exited, which is all anyone will ever see of it.
        stat = _proc_stat(self.pid)
        if stat is not None and stat[0] == "Z" and stat[1] != self._ppid:
            return 0
        return None

    def send_signal(self, signum: int) -> None:
        os.kill(self.pid, signum)

    def terminate(self) -> None:
        try:
            os.kill(self.pid, 15)
        except OSError:
            pass

    def kill(self) -> None:
        try:
            os.kill(self.pid, 9)
        except OSError:
            pass

    def wait(self, timeout: "float | None" = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() > deadline:
                raise subprocess.TimeoutExpired("zygote-child", timeout)
            time.sleep(0.02)
        return 0


class WorkerExit(NamedTuple):
    """What ``end_workers`` saw of one process."""

    exit_code: "int | None"    # None: died of a signal, or a PidHandle
    term_signal: "int | None"
    seconds: float             # from the signal to the process being gone
    escalated: bool            # it took SIGKILL
    gone: bool                 # False: still there when the bound passed


def split_returncode(rc: "int | None") -> "tuple[int | None, int | None]":
    """Popen.returncode -> (exit_code, term_signal)."""
    if rc is None:
        return None, None
    return (rc, None) if rc >= 0 else (None, -rc)


def end_workers(workers) -> "list[WorkerExit | None]":
    """End local worker processes; return when each has been waited for.

    ``workers``: ``(proc, conn, tpu_capable)`` triples: a Popen or
    ``PidHandle``, the worker's connection (None: none yet, or the
    caller is not its peer) and whether it was spawned able to open the
    chips. A worker of another machine has no ``proc`` here: it gets the
    cast alone and None for an exit, and the agent that spawned it
    reaps. All are told to go first (the ``kill`` cast where there is a
    connection, SIGTERM where there is only a pid), then each is waited
    for, SIGKILLed once its grace is over, and reaped. A chipless worker
    shares ``CHIPLESS_GRACE_S`` with the others of the call; a
    ``tpu_capable`` one may have the chips open, which the kernel takes
    seconds to close and SIGKILL does not hurry, so it gets
    ``CHIP_HOLDER_GRACE_S``. Either way the process is then waited for
    until it is gone, under ``CHIP_RELEASE_BOUND_S``; one that outlasts
    the bound gets a line on stderr and ``gone=False``."""
    workers = list(workers)
    t0 = time.monotonic()
    for proc, conn, _ in workers:
        if proc is not None and proc.poll() is not None:
            continue
        if conn is not None:
            try:
                conn.cast("kill", {})
            except rpc.ConnectionLost:
                pass  # it is on its way out, or the grace below finds it
        elif proc is not None:
            proc.terminate()
    exits = []
    for proc, _, tpu_capable in workers:
        if proc is None:
            exits.append(None)
            continue
        grace = CHIP_HOLDER_GRACE_S if tpu_capable else CHIPLESS_GRACE_S
        escalated, gone = False, True
        try:
            proc.wait(timeout=max(0.05, t0 + grace - time.monotonic()))
        except subprocess.TimeoutExpired:
            escalated = True
            proc.kill()
            try:
                proc.wait(timeout=max(
                    0.05, t0 + CHIP_RELEASE_BOUND_S - time.monotonic()))
            except subprocess.TimeoutExpired:
                gone = False
        seconds = time.monotonic() - t0
        if not gone:
            stat = _proc_stat(proc.pid)
            print(f"[ray_tpu] worker pid {proc.pid} is still there "
                  f"{seconds:.1f} s after it was told to go, SIGKILL "
                  f"included (state {stat[0] if stat else 'unknown'}); "
                  + ("its chips are not free" if tpu_capable
                     else "left behind"), file=sys.stderr, flush=True)
        exits.append(WorkerExit(
            *split_returncode(proc.returncode if gone else None),
            seconds, escalated, gone))
    return exits


def await_chips_free(nodes) -> float:
    """Return once every device node in ``nodes`` can be opened, i.e.
    the last holder of those chips has let go; the seconds waited. The
    open is what libtpu will do next, so a holder in another container
    or namespace counts too, which no search of /proc would find. EBUSY
    means wait and try again, under ``CHIP_RELEASE_BOUND_S``; any other
    error (no permission, no such node) means "cannot tell" and counts
    as free, so libtpu reports it as it always has."""
    t0 = time.monotonic()
    busy = [n for n in nodes if _node_busy(n)]
    if not busy:
        return 0.0
    while True:
        time.sleep(0.1)
        busy = [n for n in busy if _node_busy(n)]
        waited = time.monotonic() - t0
        if not busy:
            return waited
        if waited >= CHIP_RELEASE_BOUND_S:
            raise RuntimeError(
                f"device node(s) {', '.join(busy)} still busy after "
                f"{waited:.1f} s: another process holds these chips")


def _node_busy(path: str) -> bool:
    try:
        fd = os.open(path, os.O_RDWR | os.O_CLOEXEC)
    except OSError as e:
        return e.errno == errno.EBUSY
    os.close(fd)
    return False
