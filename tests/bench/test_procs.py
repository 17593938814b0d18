"""No run leaves a process, however it ends: the launcher killed or
signalled in its window, the serve phase raising after it, and a leftover
of an earlier run recorded in the scratch directory. CPU rehearsals; each
run is told apart by a seed of its own in its command line, and every wait
has its own deadline."""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from benchmarks import harness, procs


def with_seed(seed: int) -> list:
    """Pids of the live processes with this run's seed in the command line."""
    return [int(p) for p in os.listdir("/proc")
            if p.isdigit() and f"--seed {seed} " in procs.cmdline(int(p)) + " "]


def launch(tmp_path, seed: int, seconds: int, *extra):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    out, err = open(tmp_path / "out", "w"), open(tmp_path / "err", "w")
    proc = subprocess.Popen(
        [sys.executable, "benchmarks/run.py", "--workload", "dsv2l-decode-long", "--seed", str(seed),
         "--seconds", str(seconds), "--trace", "0", "--rehearse-cpu", *extra],
        cwd=harness.ROOT, env=env, stdout=out, stderr=err)
    out.close(), err.close()
    return proc


def wait_for(what, found, timeout: float):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = found()
        if got:
            return got
        time.sleep(0.1)
    pytest.fail(f"{what} not seen within {timeout:.0f} s")


def load_generator(seed: int):
    """(pid, port) of the run's load generator, once the serve phase has
    started it: the server is up, every shape is warm, the ramp begins."""
    for pid in with_seed(seed):
        words = procs.cmdline(pid).split()
        if any(w.endswith("client.py") for w in words):
            return pid, int(words[words.index("--port") + 1])
    return None


def assert_nothing_left(seed: int, port: int, within: float = 10.0):
    deadline = time.time() + within
    while with_seed(seed) and time.time() < deadline:
        time.sleep(0.1)
    left = {p: procs.cmdline(p)[:120] for p in with_seed(seed)}
    assert not left, f"alive {within:.0f} s after the launcher ended: {left}"
    # A process that is being killed loses its command line before its sockets.
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=2).close()
        except (ConnectionRefusedError, ConnectionResetError):
            return  # reset: a port in the act of closing, met once under the whole suite's load
        time.sleep(0.1)
    pytest.fail(f"port {port} still takes connections {within:.0f} s after the launcher ended")


def result_lines(tmp_path) -> list:
    return [ln for ln in (tmp_path / "out").read_text().splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("sig, seed", [(signal.SIGKILL, 927101), (signal.SIGTERM, 927102)],
                         ids=["kill-9", "sigterm"])
def test_launcher_ended_in_its_window_takes_the_run_along(tmp_path, sig, seed):
    """``kill -9`` leaves the launcher no word: the kernel kills each phase
    and the load generator as their parent dies. SIGTERM (and SIGINT,
    SIGHUP: one handler) it answers itself: it kills the phase's process
    group, waits for it, and exits non-zero without a result."""
    proc = launch(tmp_path, seed, 60)
    try:
        _, port = wait_for("the load generator", lambda: load_generator(seed), 240)
        time.sleep(2.0)  # past the ramp, requests in flight
        assert len(with_seed(seed)) == 3, "launcher, serve phase, load generator"
        proc.send_signal(sig)
        rc = proc.wait(timeout=20)
        assert rc == (-signal.SIGKILL if sig == signal.SIGKILL else 128 + signal.SIGTERM)
        assert_nothing_left(seed, port)
        assert not result_lines(tmp_path)
    finally:
        for pid in with_seed(seed):
            os.kill(pid, signal.SIGKILL)
        proc.wait(timeout=20)


def test_serve_phase_that_raises_ends_its_process(tmp_path):
    """``--break raise``: an exception after the window, with the server up
    and its scheduler driving the device. The traceback is printed, the
    phase leaves by ``os._exit``, the launcher exits non-zero within 30 s
    of the window's end, prints no result, and nothing is left."""
    seed, seconds = 927103, 4
    mix = harness.load_json(harness.traffic_path("reason-long"))["rehearse"]
    proc = launch(tmp_path, seed, seconds, "--break", "raise")
    try:
        _, port = wait_for("the load generator", lambda: load_generator(seed), 240)
        window_over = time.time() + mix["ramp_s"] + 0.5 + seconds + mix["drain_s"]
        rc = proc.wait(timeout=mix["ramp_s"] + seconds + mix["drain_s"] + 60)
        assert rc not in (0, None)
        assert time.time() < window_over + 30
        err = (tmp_path / "err").read_text()
        assert "RuntimeError: --break raise" in err and "the serve phase exited 1; no result" in err
        assert not result_lines(tmp_path)
        assert_nothing_left(seed, port)
    finally:
        for pid in with_seed(seed):
            os.kill(pid, signal.SIGKILL)
        proc.wait(timeout=20)


def test_next_launcher_kills_a_recorded_leftover(tmp_path):
    """A process an earlier launcher started and left alive, still a
    ``benchmarks/`` process by its command line, is killed when the next
    launcher starts, and named on stderr; one whose pid now belongs to
    something else is left alone, as is the record of a launcher that
    still runs."""
    def sleeper(tag):
        return subprocess.Popen([sys.executable, "-c", "import time; time.sleep(600)", tag])

    left, other, beside = sleeper("benchmarks/left-over"), sleeper("not-a-bench-mark"), sleeper("benchmarks/beside")
    running = sleeper("benchmarks/launcher-that-still-runs")
    try:
        wait_for("the sleepers' command lines", lambda: all(
            "time.sleep" in procs.cmdline(p.pid) for p in (left, other, beside, running)), 20)
        dead = subprocess.Popen([sys.executable, "-c", "pass"])
        dead.wait(timeout=20)
        pids = tmp_path / "pids"
        pids.mkdir()
        (pids / f"{dead.pid}.json").write_text(json.dumps({"launcher": dead.pid, "started": [left.pid, other.pid]}))
        (pids / f"{running.pid}.json").write_text(json.dumps({"launcher": running.pid, "started": [beside.pid]}))
        (pids / "torn.json").write_text('{"launcher": 1, "star')
        code = ("import sys; from benchmarks import procs\n"
                f"with procs.Launcher({str(tmp_path)!r}):\n    pass\n")
        got = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT, capture_output=True, text=True, timeout=60)
        assert got.returncode == 0, got.stderr[-2000:]
        assert left.wait(timeout=10) == -signal.SIGKILL
        assert f"killing pid {left.pid}" in got.stderr and "benchmarks/left-over" in got.stderr
        assert str(other.pid) not in got.stderr and str(beside.pid) not in got.stderr
        assert other.poll() is None and beside.poll() is None and running.poll() is None
        assert sorted(os.listdir(pids)) == sorted([f"{running.pid}.json", "torn.json"])
    finally:
        for p in (left, other, beside, running):
            p.kill()
            p.wait(timeout=20)


def test_child_whose_parent_is_gone_leaves_at_once():
    """The look after the ``prctl``: a child that finds another parent than
    the one that started it (which died before the child could ask the
    kernel) exits; one that finds it carries on and is killed with it."""
    code = "from benchmarks import procs; procs.die_with_parent(); print('on'); import time; time.sleep(600)"
    env = {**os.environ, procs.PARENT_ENV: "1"}
    gone = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT, env=env, capture_output=True, text=True,
                          timeout=60)
    assert gone.returncode == 1 and "on" not in gone.stdout and "is gone" in gone.stderr
    # A parent that starts the child and is then killed: the child goes with it.
    parent = ("import subprocess, sys, time; from benchmarks import procs\n"
              f"c = subprocess.Popen([sys.executable, '-c', {code!r}], env=procs.child_env(), stdout=subprocess.PIPE)\n"
              "c.stdout.readline(); print(c.pid, flush=True); time.sleep(600)\n")
    p = subprocess.Popen([sys.executable, "-c", parent], cwd=harness.ROOT, stdout=subprocess.PIPE, text=True)
    try:
        child = int(p.stdout.readline())
        assert "die_with_parent" in procs.cmdline(child)
        p.kill()
        p.wait(timeout=20)
        wait_for("the child's end", lambda: "die_with_parent" not in procs.cmdline(child), 10)
    finally:
        p.kill()
        p.wait(timeout=20)


def test_exit_after_ends_the_process_on_every_path():
    """Return code, exception (traceback printed, code 1) and SystemExit
    alike end by ``os._exit``: nothing after the call runs, not even a
    ``finally`` or an ``atexit`` of the caller."""
    head = "import atexit, sys; from benchmarks import procs; atexit.register(lambda: print('teardown'))\n"
    cases = {
        "procs.exit_after(lambda: 5)": (5, ""),
        "procs.exit_after(lambda: None)": (0, ""),
        "procs.exit_after(lambda: 1 / 0)": (1, "ZeroDivisionError"),
        "procs.exit_after(lambda: sys.exit(7))": (7, ""),
        "procs.exit_after(lambda: sys.exit('no such cell'))": (1, "no such cell"),
    }
    for call, (code, said) in cases.items():
        got = subprocess.run([sys.executable, "-c", head + f"try:\n    print('in', flush=False); {call}\n"
                              "finally:\n    print('after')\n"],
                             cwd=harness.ROOT, capture_output=True, text=True, timeout=60)
        assert got.returncode == code, (call, got.returncode, got.stderr)
        assert got.stdout == "in\n", "flushed, and nothing ran after"
        assert said in got.stderr
