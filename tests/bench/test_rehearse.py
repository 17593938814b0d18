"""The benchmark's whole control flow on the CPU at tiny widths: every
cell through the launcher, the serve phase and the check phase; the result
line's keys; no metric from a run that found no TPU; ``correct`` false when
the timed path is broken underneath; and the control of ``correct``."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(cell, *extra, seed=5, seconds=4):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", "1", "--rehearse-cpu", *extra],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


@pytest.mark.parametrize("cell", [w["name"] for w in harness.load_benchmark()["workloads"]])
def test_rehearsal_of_each_cell(cell):
    proc, lines = run_cell(cell)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(lines[-1])
    assert CONTRACT_KEYS <= set(result)
    assert set(result) <= CONTRACT_KEYS | {"breakdown", "rehearsal", "compared"}
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}, "a CPU run prints no metric under a device metric's name"
    assert "rehearsal" in result and "REHEARSAL" in proc.stdout
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    shown = {ln.split()[2].split("=")[0] for ln in lines if ln.startswith("bench: compare ")}
    assert shown == {"requests_failed", "replies_malformed", "compiled_in_window", "logit_noise",
                     "gap_max", "gap_mean"}
    # Each number compared beside its limit: the result line's last key, and standard error's last lines.
    assert list(result)[-1] == "compared" and set(result["compared"]) == shown
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    last = [ln for ln in proc.stderr.splitlines() if ln.strip()][-len(shown):]
    assert [ln.split()[2].split("=")[0] for ln in last] == list(result["compared"])
    assert "self seconds by phase" in proc.stdout and '"serve_wait"' in proc.stdout
    # The three counts of tokens, as counts (no rate on a CPU): which is which is ``stats.window_stats``'s to say.
    assert all(f'"{k}": ' in proc.stdout for k in ("offered_tokens", "tokens_of_due", "tokens_in_window"))
    for name in ("tokens_per_s_per_chip", "tpot_p50_ms", "ttft_p50_ms", "setup_s "):
        assert f'"{name}' not in proc.stdout.replace("setup_s ", "")


def compared(lines):
    return {ln.split()[2].split("=")[0]: float(ln.split()[2].split("=")[1])
            for ln in lines if ln.startswith("bench: compare ")}


def test_no_tpu_no_result():
    """Without --rehearse-cpu the run looks for a TPU, finds none here,
    exits non-zero and prints no result."""
    env = {k: v for k, v in os.environ.items()}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "dsv2l-decode-long", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_broken_timed_path_is_not_correct():
    """Every token altered where the pools sample it: the replies are well
    formed, nothing compiles in the window, and ``correct`` is false on
    the comparison with the reference alone: the served tokens lie far
    below the reference's best."""
    proc, lines = run_cell("dsv2l-decode-long", "--break", "token")
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert "compare replies_malformed=0" in proc.stdout
    assert "compare compiled_in_window=0" in proc.stdout
    assert "compare requests_failed=0" in proc.stdout
    assert compared(lines)["gap_mean"] > 1.0, "an altered token lies units below the reference's best"


@pytest.mark.parametrize("cell", [w["name"] for w in harness.load_benchmark()["workloads"]])
def test_control_runs_the_programs_own_int8_path(cell):
    """The control of ``correct`` is the cell with the program's own
    weight-only int8 path on (TPUFW_QUANTIZE=int8). Here, at tiny widths,
    the whole run is driven with it: the server quantizes the benchmark's
    weights, serves every request, nothing compiles in the window, and the
    comparison prints its numbers. Whether they pass the limits is decided
    on the chip at the cells' own size (PERF.md section 2): a few hundred
    tokens of a toy model swing the gaps of sound runs as far as int8 does.
    ``test_ids_alone_tell_a_lower_precision_apart`` shows why a thousand
    tokens of the real size do tell them apart."""
    proc, lines = run_cell(cell, "--control", "int8_weights", seconds=8)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "'TPUFW_QUANTIZE': 'int8'" in proc.stdout
    got = compared(lines)
    assert got["requests_failed"] == got["replies_malformed"] == got["compiled_in_window"] == 0
    assert got["gap_max"] >= got["gap_mean"] >= 0 and got["logit_noise"] > 0
