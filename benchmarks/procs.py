"""The processes of a run, and the promise that none outlives it.

A run is a launcher, its phases one after the other, and a load generator
under the serving phase. A leftover of any of them can hold the chip or
answer the next run's requests, so three things hold together, whichever
way a run ends:

- every child asks the kernel, as it starts, to be killed when the process
  that started it dies (``die_with_parent``), so ``kill -9`` of the launcher
  takes the tree along;
- the launcher starts each phase in a process group of its own and, on
  every way out (return, exception, SIGTERM, SIGINT, SIGHUP), kills that
  group and waits until it is empty (``Launcher``);
- the launcher records the pids it started under the scratch directory, and
  the next launcher there kills whichever of them is still alive and still
  a benchmark process by its command line, and says so.

A phase that holds the device leaves by ``os._exit`` on every path
(``exit_after``): a thread of the program under test may still drive the
device, and the interpreter's teardown would race it.

Linux only, like the chip. Nothing here imports jax."""

from __future__ import annotations

import ctypes
import json
import os
import signal
import subprocess
import sys
import time
import traceback

PR_SET_PDEATHSIG = 1
#: How a child learns who started it: a parent that died before the child
#: could ask the kernel has left it another parent already.
PARENT_ENV = "BENCH_PARENT_PID"
#: What a benchmark process has in its command line (the launcher, a phase,
#: the load generator: each is started by a path under ``benchmarks/``).
MARK = "benchmarks/"
SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)
GONE_WAIT_S = 10.0


def child_env() -> dict:
    return {**os.environ, PARENT_ENV: str(os.getpid())}


def die_with_parent() -> None:
    """SIGKILL for this process when the one that started it dies; at once
    if it has died already."""
    libc = ctypes.CDLL(None, use_errno=True)
    libc.prctl.argtypes = [ctypes.c_int] + [ctypes.c_ulong] * 4
    libc.prctl.restype = ctypes.c_int
    if libc.prctl(PR_SET_PDEATHSIG, int(signal.SIGKILL), 0, 0, 0) != 0:
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_PDEATHSIG) failed")
    parent = os.environ.get(PARENT_ENV)
    if parent and os.getppid() != int(parent):
        print(f"bench: the process that started this one ({parent}) is gone; leaving", file=sys.stderr, flush=True)
        os._exit(1)


def exit_after(fn) -> None:
    """Run ``fn`` and end the process with its return code by ``os._exit``,
    whichever way ``fn`` ends: an exception prints its traceback and is
    code 1. Never returns."""
    code = 1
    try:
        code = int(fn() or 0)
    except SystemExit as e:
        if e.code is None or isinstance(e.code, int):
            code = e.code or 0
        else:
            print(e.code, file=sys.stderr)
    except BaseException:
        traceback.print_exc()
    finally:
        for stream in (sys.stdout, sys.stderr):
            try:
                stream.flush()
            except (OSError, ValueError):
                pass
        os._exit(code)


def cmdline(pid: int) -> str:
    """The process's command line, "" where it is gone (or going)."""
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode(errors="replace").strip()
    except OSError:
        return ""


def _state_and_group(pid):
    """(state letter, process group) from /proc, None where the pid is free."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            state, _ppid, pgrp = f.read().rpartition(")")[2].split()[:3]
        return state, int(pgrp)
    except (OSError, ValueError):
        return None


def alive(pid: int) -> bool:
    """Whether the process still holds anything. One that is being killed
    loses its command line before its files and devices, so the command
    line cannot say; its state can: a zombie has let go of everything."""
    got = _state_and_group(pid)
    return got is not None and got[0] not in "ZX"


def group_members(pgid: int) -> list:
    """Pids of the live (not zombie) processes of a process group."""
    out = []
    for name in os.listdir("/proc"):
        got = _state_and_group(name) if name.isdigit() else None
        if got is not None and got[1] == pgid and got[0] not in "ZX":
            out.append(int(name))
    return out


def _wait_gone(still, what: str) -> None:
    deadline = time.time() + GONE_WAIT_S
    while still():
        if time.time() > deadline:
            print(f"bench: {what} still alive {GONE_WAIT_S:.0f} s after SIGKILL", file=sys.stderr, flush=True)
            return
        time.sleep(0.02)


def kill_group(pgid: int) -> None:
    """SIGKILL a process group and wait until no live process is in it."""
    try:
        os.killpg(pgid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    _wait_gone(lambda: group_members(pgid), f"process group {pgid}")


class Launcher:
    """Starts a run's phases; see the module's text. Use as a context
    manager around everything the launcher does once it may start one."""

    def __init__(self, scratch: str):
        self.dir = os.path.join(scratch, "pids")
        self.file = os.path.join(self.dir, f"{os.getpid()}.json")
        self.started: list = []
        self.live = None
        self._handlers: dict = {}

    def __enter__(self):
        os.makedirs(self.dir, exist_ok=True)
        self._sweep()
        for s in SIGNALS:
            self._handlers[s] = signal.signal(s, self._on_signal)
        return self

    def __exit__(self, *exc) -> None:
        for s in SIGNALS:  # nothing may cut the clean-up short
            signal.signal(s, signal.SIG_IGN)
        self._reap()
        try:
            os.remove(self.file)
        except FileNotFoundError:
            pass
        for s, old in self._handlers.items():
            signal.signal(s, old)

    @staticmethod
    def _on_signal(signum, frame) -> None:
        raise SystemExit(128 + signum)

    def run(self, cmd: list, cwd: str) -> int:
        """One phase in a process group of its own, waited for; whatever it
        left in that group is killed before this returns."""
        self.live = subprocess.Popen(cmd, cwd=cwd, env=child_env(), process_group=0)
        self.started.append(self.live.pid)
        self._record()
        rc = self.live.wait()
        self._reap()
        return rc

    def _reap(self) -> None:
        if self.live is not None:
            kill_group(self.live.pid)
            self.live.wait()
            self.live = None

    def _record(self) -> None:
        tmp = self.file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"launcher": os.getpid(), "started": self.started}, f)
        os.replace(tmp, self.file)

    def _sweep(self) -> None:
        """Kill what an earlier launcher here started and left alive. A
        record whose launcher still runs is a run beside this one, and is
        left alone."""
        for name in sorted(os.listdir(self.dir)):
            path = os.path.join(self.dir, name)
            try:
                with open(path) as f:
                    rec = json.load(f)
                launcher, started = int(rec["launcher"]), [int(p) for p in rec["started"]]
            except (OSError, ValueError, KeyError, TypeError):
                continue  # another launcher is writing or has removed it
            if launcher != os.getpid() and MARK in cmdline(launcher):
                continue
            for pid in started:
                line = cmdline(pid)
                if MARK in line:
                    print(f"bench: killing pid {pid}, left alive by an earlier run (launcher {launcher}): "
                          f"{line[:200]}", file=sys.stderr, flush=True)
                    try:
                        if os.getpgid(pid) == pid:
                            kill_group(pid)
                        else:
                            os.kill(pid, signal.SIGKILL)
                    except (ProcessLookupError, PermissionError):
                        pass
                elif line:
                    continue  # the pid is another program's by now
                # Killed by us or a moment ago with its launcher: it holds the chip until it is gone.
                _wait_gone(lambda: alive(pid), f"pid {pid}")
            try:
                os.remove(path)
            except FileNotFoundError:
                pass
