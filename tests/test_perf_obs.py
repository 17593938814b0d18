"""Perf observatory tests: cost harvest through the real jit AOT path
on CPU, MFU/roofline gauge math against a hand-computed fixture, the
cross-host trace merge's clock alignment, the profiler-hook window
resolution, and the disabled-path overhead budget."""

import json
import os
import sys
import time

import pytest

from tpufw.obs.perf import (
    NULL,
    PerfObservatory,
    ProfileTrigger,
    load_programs,
    parse_profile_steps,
    resolve_profile_window,
)
from tpufw.obs.registry import Registry
from tpufw.obs.roofline import (
    PeakSpec,
    attainable_flops_per_s,
    classify,
    detect_peaks,
)

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "scripts"),
)

import trace_merge  # noqa: E402  (scripts/ is not a package)


# ---------------------------------------------------------- cost harvest


def test_observe_jit_harvests_costs_on_cpu(tmp_path):
    """The real AOT path: observe a jitted matmul, expect FLOPs/bytes
    in the table and a parseable programs.json. Backends without an
    HLO cost model return empty analyses — skip, don't fail (ISSUE 9
    acceptance wording)."""
    import jax
    import jax.numpy as jnp

    obs = PerfObservatory(registry=Registry(), out_dir=str(tmp_path))
    x = jnp.ones((64, 64), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    obs.observe_jit("matmul", f, (x,))
    snap = obs.snapshot()
    assert "matmul" in snap
    assert "error" not in snap["matmul"], snap["matmul"]
    doc = load_programs(str(tmp_path))
    assert doc is not None and "matmul" in doc["programs"]
    if not snap["matmul"].get("flops"):
        pytest.skip("cost_analysis empty on this backend")
    # 64x64x64 matmul: 2*N^3 FLOPs (XLA counts fused multiply-adds
    # as 2); allow the backend some slack but demand the right scale.
    assert snap["matmul"]["flops"] == pytest.approx(2 * 64**3, rel=0.5)
    # Harvest is once-per-name: a second observe is a no-op even with
    # a different callable.
    obs.observe_jit("matmul", None)
    assert obs.snapshot()["matmul"] == snap["matmul"]


def test_observe_jit_failure_records_error_and_never_raises(tmp_path):
    obs = PerfObservatory(out_dir=str(tmp_path))
    obs.observe_jit("broken", object())  # no .lower -> harvest fails
    snap = obs.snapshot()
    assert "error" in snap["broken"]
    # and the failure is latched, not retried
    obs.observe_jit("broken", object())
    assert obs.snapshot()["broken"] == snap["broken"]


# ------------------------------------------------------ MFU gauge math


def _fixture_obs(registry=None):
    # Hand-computable peaks: 1 TFLOP/s, 100 GB/s (balance = 10
    # FLOPs/byte), 16 GB HBM.
    peaks = PeakSpec(
        chip="test",
        flops_per_s=1e12,
        hbm_bw_bytes_per_s=1e11,
        hbm_bytes=16_000_000_000,
    )
    return PerfObservatory(registry=registry, peaks=peaks)


def test_mfu_and_roofline_gauges_match_hand_computation():
    reg = Registry()
    obs = _fixture_obs(reg)
    obs.record_costs(
        "p",
        flops=2e9,
        bytes_accessed=1e9,
        memory={
            "argument_bytes": 4_000_000_000,
            "output_bytes": 1_000_000_000,
            "temp_bytes": 2_000_000_000,
            "alias_bytes": 1_000_000_000,
        },
    )
    # AI = 2e9/1e9 = 2 FLOPs/byte, below the balance point 10 ->
    # memory-bound.
    assert reg.gauge("tpufw_program_ai").value(program="p") == 2.0
    assert reg.gauge("tpufw_program_compute_bound").value(program="p") == 0
    # peak HBM = 4 + 1 + 2 - 1 = 6 GB -> headroom = 16 - 6 = 10 GB.
    assert reg.gauge("tpufw_hbm_headroom_bytes").value() == 10_000_000_000
    # 2e9 FLOPs in 4 ms on a 1 TFLOP/s chip = 0.5 MFU.
    mfu = obs.record_wall("p", 0.004)
    assert mfu == pytest.approx(0.5)
    assert reg.gauge("tpufw_program_mfu").value(program="p") == (
        pytest.approx(0.5)
    )
    # attrib surfaces the same numbers for bench/goodput.
    at = obs.attrib("p")
    assert at["measured_mfu"] == pytest.approx(0.5)
    assert at["roofline_bound"] == "memory"
    assert at["hbm_headroom_bytes"] == 10_000_000_000


def test_record_wall_unknown_or_flopless_program_returns_none():
    obs = _fixture_obs()
    assert obs.record_wall("nope", 0.1) is None
    obs.record_costs("zero", flops=0.0, bytes_accessed=0.0)
    assert obs.record_wall("zero", 0.1) is None
    assert obs.record_wall("zero", -1.0) is None


def test_roofline_classify_and_attainable():
    peaks = PeakSpec("t", 1e12, 1e11, 0)
    assert classify(2.0, peaks) == "memory"
    assert classify(10.0, peaks) == "compute"
    assert classify(None, peaks) is None
    assert classify(1.0, PeakSpec("t", 1e12, 0.0, 0)) is None
    assert attainable_flops_per_s(2.0, peaks) == 2e11
    assert attainable_flops_per_s(1e6, peaks) == 1e12


def test_detect_peaks_on_cpu_has_no_peaks():
    # No table row for a CPU: every peak reads 0 = unknown, and the
    # observatory leaves MFU, bound and headroom out instead of
    # dividing by an invented chip.
    peaks = detect_peaks()
    assert (peaks.flops_per_s, peaks.hbm_bw_bytes_per_s, peaks.hbm_bytes) == (
        0.0, 0.0, 0
    )
    obs = PerfObservatory(registry=Registry())
    obs.record_costs(
        "p", flops=2e9, bytes_accessed=1e9,
        memory={"argument_bytes": 4_000_000_000},
    )
    assert obs.record_wall("p", 0.004) is None
    assert obs.attrib("p") == {"program": "p"}
    assert obs.snapshot()["p"]["wall_s"] == 0.004


# ------------------------------------------------------ programs.json


def test_load_programs_torn_file_returns_none(tmp_path):
    assert load_programs(str(tmp_path)) is None  # missing
    with open(os.path.join(tmp_path, "programs.json"), "w") as f:
        f.write('{"programs": {"x": ')  # torn mid-write
    assert load_programs(str(tmp_path)) is None


# -------------------------------------------------------- trace merge


def _trace_doc(wall0, spans, name):
    return {
        "traceEvents": [
            {
                "name": "process_name",
                "ph": "M",
                "pid": 0,
                "args": {"name": name},
            }
        ]
        + [
            {"name": n, "ph": "X", "ts": ts, "dur": d, "pid": 0, "tid": 1}
            for n, ts, d in spans
        ],
        "displayTimeUnit": "ms",
        "otherData": {"wall_epoch_s": wall0, "dropped_events": 0},
    }


def test_trace_merge_aligns_two_hosts(tmp_path):
    # Host B started 0.5 s after host A; both stamped local ts from 0.
    a = tmp_path / "trace.json"
    b = tmp_path / "trace-p1.json"
    a.write_text(json.dumps(_trace_doc(
        100.0, [("step", 0.0, 10.0), ("step", 2_000_000.0, 10.0)], "a"
    )))
    b.write_text(json.dumps(_trace_doc(
        100.5, [("step", 0.0, 10.0), ("step", 1_000_000.0, 10.0)], "b"
    )))
    out = tmp_path / "merged.json"
    rc = trace_merge.main([str(tmp_path), "-o", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    # Aligned: host B's t=0 lands at +500000 us on the shared axis,
    # and the merged stream is ts-monotonic.
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    assert ts == [0.0, 500_000.0, 1_500_000.0, 2_000_000.0]
    # Hosts keep distinct pids (distinct Perfetto tracks).
    assert {e["pid"] for e in evs} == {0, 1}
    assert doc["otherData"]["wall_epoch_s"] == 100.0
    assert sorted(doc["otherData"]["merged_from"]) == [
        "trace-p1.json", "trace.json",
    ]


def test_trace_merge_skips_torn_file(tmp_path):
    good = tmp_path / "trace.json"
    good.write_text(json.dumps(_trace_doc(1.0, [("s", 0.0, 1.0)], "g")))
    (tmp_path / "trace-p1.json").write_text('{"traceEvents": [')
    out = tmp_path / "merged.json"
    assert trace_merge.main([str(tmp_path), "-o", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["otherData"]["merged_from"] == ["trace.json"]


def test_trace_merge_no_inputs_fails_cleanly(tmp_path):
    assert trace_merge.main([str(tmp_path)]) == 1


# ---------------------------------------------------- profiler window


def test_parse_profile_steps():
    assert parse_profile_steps("3:6") == (3, 6)
    assert parse_profile_steps("") is None
    assert parse_profile_steps("junk") is None
    assert parse_profile_steps("6:3") is None
    assert parse_profile_steps("-1:2") is None


def test_resolve_profile_window_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("TPUFW_PROFILE_STEPS", "4:9")
    d, a, b = resolve_profile_window(
        None, 3, 6, telemetry_dir=str(tmp_path)
    )
    assert (a, b) == (4, 9)
    assert d == os.path.join(str(tmp_path), "xprof")
    monkeypatch.delenv("TPUFW_PROFILE_STEPS")
    d, a, b = resolve_profile_window("/tmp/x", 3, 6, telemetry_dir=None)
    assert (d, a, b) == ("/tmp/x", 3, 6)


def test_profile_trigger_rejects_concurrent_capture(tmp_path):
    trig = ProfileTrigger(str(tmp_path))
    with trig._lock:
        trig._active = True
    assert trig.trigger(0.1) == {"error": "capture already in progress"}


# ------------------------------------------- disabled-overhead budget


def test_null_observatory_per_step_overhead_below_1pct():
    """TPUFW_PERF_OBS=0 path: the per-step probe calls (observe_jit +
    record_wall on the null object) must cost well under 1% of the
    repo's smallest real step (~25 ms on CPU -> 250 us). Budget 100 us,
    same discipline as test_obs.py's disabled-telemetry budget."""
    assert not NULL.enabled
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        NULL.observe_jit("train_step", None, (1, 2))
        NULL.record_wall("train_step", 0.01)
    per_step = (time.perf_counter() - t0) / n
    assert per_step < 100e-6, f"null perf obs {per_step*1e6:.1f}us/step"
    assert NULL.attrib() == {} and NULL.snapshot() == {}
