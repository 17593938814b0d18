"""Unified telemetry (tpufw.obs): registry exposition, event-log schema
round-trip, Chrome-trace validity, straggler detection, and the
end-to-end trainer acceptance — metrics served over HTTP mid-run,
schema-valid events.jsonl, spans covering the step loop's wall-clock,
and a <1% per-step cost when disabled."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from tpufw.obs import Telemetry
from tpufw.obs import events as events_mod
from tpufw.obs import trace as trace_mod
from tpufw.obs.registry import Registry, start_http_server
from tpufw.obs.skew import SkewMonitor


# ---------------------------------------------------------------- registry


def test_registry_exposition_format():
    r = Registry()
    r.counter("tpufw_x_total", "help text").inc(3)
    r.counter("tpufw_big_total").inc(123456789)
    r.gauge("tpufw_g").set(1.5)
    h = r.histogram("tpufw_t_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = r.render()
    lines = text.splitlines()
    assert "# HELP tpufw_x_total help text" in lines
    assert "# TYPE tpufw_x_total counter" in lines
    assert "tpufw_x_total 3" in lines
    # repr formatting, not %g: large counters must not lose precision.
    assert "tpufw_big_total 123456789" in lines
    assert "# TYPE tpufw_g gauge" in lines
    assert "tpufw_g 1.5" in lines
    # Cumulative buckets + +Inf + sum/count.
    assert 'tpufw_t_seconds_bucket{le="0.1"} 1' in lines
    assert 'tpufw_t_seconds_bucket{le="1"} 2' in lines
    assert 'tpufw_t_seconds_bucket{le="+Inf"} 3' in lines
    assert "tpufw_t_seconds_count 3" in lines
    assert text.endswith("\n")


def test_counter_preinitialized_and_labels():
    r = Registry()
    c = r.counter("tpufw_errs_total")
    # Absent-series rationale: the unlabeled series exists at 0 before
    # any inc, so increase() alerts can fire on the first error.
    assert "tpufw_errs_total 0" in r.render()
    c.inc(2, host=1)
    assert 'tpufw_errs_total{host="1"} 2' in r.render()
    assert c.value(host=1) == 2
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_kind_collision():
    r = Registry()
    r.counter("tpufw_thing")
    with pytest.raises(TypeError):
        r.gauge("tpufw_thing")


def test_registry_get_or_create_is_idempotent():
    r = Registry()
    assert r.counter("c") is r.counter("c")
    r.counter("c").inc()
    assert r.counter("c").value() == 1


def test_gauge_set_function_evaluated_at_scrape():
    r = Registry()
    val = {"v": 1.0}
    r.gauge("tpufw_depth").set_function(lambda: val["v"])
    assert "tpufw_depth 1" in r.render()
    val["v"] = 7.0
    assert "tpufw_depth 7" in r.render()


def test_counter_thread_safety():
    r = Registry()
    c = r.counter("tpufw_n_total")

    def work():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8000


def test_histogram_observe_n_aggregates_exactly():
    r = Registry()
    h = r.histogram("tpufw_w_seconds", buckets=(0.01, 0.1, 1.0))
    h.observe(0.05, n=4)  # a 4-step window's per-step average
    assert h.value() == 4
    text = r.render()
    assert 'tpufw_w_seconds_bucket{le="0.1"} 4' in text
    assert "tpufw_w_seconds_sum 0.2" in text


def test_http_endpoint_serves_prometheus_text():
    r = Registry()
    r.counter("tpufw_served_total").inc(5)
    httpd = start_http_server(r, 0, host="127.0.0.1")
    try:
        port = httpd.server_address[1]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        assert "tpufw_served_total 5" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/other", timeout=10
            )
    finally:
        httpd.shutdown()
        httpd.server_close()


# ------------------------------------------------------------------ events


def test_event_log_schema_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = events_mod.EventLog(path, host=2, process=2)
    log.emit("run_start", workload="train", total_steps=10)
    log.emit(
        "step", step=1, loss=2.5, step_time_s=0.1, data_wait_s=0.01
    )
    log.emit("checkpoint_save", step=1, forced=False, saved=True)
    log.emit("checkpoint_restore", step=1)
    log.emit("preemption_signal", level="warn", signum=15)
    log.emit("preemption_stop", level="warn", step=1)
    log.emit("tune_trial", trial=0, status="ok", median_step_s=0.2)
    log.emit("tune_result", mode="search", cache_hit=False)
    log.emit("compile_cache", dir="/tmp/cc", warm=True)
    log.emit("eval", step=1, eval_loss=3.0)
    log.emit(
        "straggler_detected",
        level="warn",
        step=4,
        straggler_hosts=[3],
        median_s=0.5,
        factor=2.0,
    )
    log.emit("run_end", steps=1)
    log.close()
    events = events_mod.read_events(path)
    assert len(events) == 12
    for ev in events:
        events_mod.validate(ev)  # raises on drift
        assert ev["host"] == 2 and ev["process"] == 2
        assert ev["ts"] > 0
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"


def test_event_log_rejects_schema_drift(tmp_path):
    log = events_mod.EventLog(str(tmp_path / "e.jsonl"))
    with pytest.raises(ValueError):
        log.emit("no_such_kind", foo=1)
    with pytest.raises(ValueError):
        log.emit("step", step=1)  # missing loss/step_time_s/data_wait_s
    with pytest.raises(ValueError):
        log.emit("run_start", level="loud", workload="train")
    log.close()


def test_event_log_min_level_filters(tmp_path):
    path = str(tmp_path / "e.jsonl")
    log = events_mod.EventLog(path, min_level="warn")
    log.emit("run_start", workload="train")  # info: dropped
    log.emit("preemption_signal", level="warn", signum=15)
    log.close()
    events = events_mod.read_events(path)
    assert [e["kind"] for e in events] == ["preemption_signal"]


def test_event_log_per_host_naming(tmp_path):
    assert events_mod.log_path(str(tmp_path), 0).endswith("events.jsonl")
    assert events_mod.log_path(str(tmp_path), 3).endswith(
        "events-p3.jsonl"
    )


def test_read_events_tolerates_torn_tail(tmp_path):
    p = tmp_path / "e.jsonl"
    p.write_text('{"kind": "run_end", "steps": 1}\n{"kind": "ru')
    assert len(events_mod.read_events(str(p))) == 1


def test_read_events_during_concurrent_writer(tmp_path):
    """The reader is used on LIVE files (obs_summary mid-run, the
    goodput ledger's prior-run scan, crash_smoke's step poll), so it
    must digest a file other threads are appending to — every event it
    returns is well-formed, even with a writer mid-line."""
    path = str(tmp_path / "e.jsonl")
    log = events_mod.EventLog(path)
    # Count-bounded writers: they must finish even when the reader
    # never keeps up (3 writers outpace 1 reader under the GIL, so a
    # reader-controlled stop flag would livelock).
    n_per_writer = 400

    def writer(tid):
        for i in range(n_per_writer):
            log.emit(
                "step", step=i, loss=1.0, step_time_s=0.1,
                data_wait_s=0.0, writer=tid,
            )

    threads = [
        threading.Thread(target=writer, args=(t,)) for t in range(3)
    ]
    for t in threads:
        t.start()
    try:
        while True:
            busy = any(t.is_alive() for t in threads)
            for ev in events_mod.read_events(path):
                events_mod.validate(ev)  # no half-parsed garbage
            if not busy:
                break
    finally:
        for t in threads:
            t.join()
    log.close()
    total = len(events_mod.read_events(path))
    assert total == 3 * n_per_writer  # every line intact
    # Mid-line kill on top of the concurrent history: the reader
    # still yields every complete line.
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"kind": "step", "st')
    assert len(events_mod.read_events(path)) == total


def test_event_listeners_observe_writes_and_never_raise(tmp_path):
    path = str(tmp_path / "e.jsonl")
    log = events_mod.EventLog(path)
    seen = []
    log.listeners.append(seen.append)
    log.listeners.append(lambda ev: 1 / 0)  # must be swallowed
    log.emit("run_start", workload="train")
    log.close()
    assert [e["kind"] for e in seen] == ["run_start"]
    assert seen[0]["workload"] == "train"


# ------------------------------------------------------------------- trace


def test_trace_chrome_json_validity(tmp_path):
    path = str(tmp_path / "trace.json")
    tracer = trace_mod.Tracer(path, pid=0, process_name="test:p0/1")
    with tracer.span("outer", step=1):
        time.sleep(0.02)
        with tracer.span("inner"):
            time.sleep(0.01)
    tracer.complete("fetch", 0.005)
    tracer.instant("marker")
    tracer.close()
    doc = json.loads(open(path).read())  # must be valid JSON
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    by_name = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(by_name) == {"outer", "inner", "fetch"}
    for ev in by_name.values():
        # The complete-event fields Perfetto requires.
        assert ev["ts"] >= 0 and ev["dur"] > 0
        assert "pid" in ev and "tid" in ev
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]
    assert by_name["outer"]["args"] == {"step": 1}
    assert abs(by_name["fetch"]["dur"] - 5000) < 4000  # ~5ms in us
    assert any(e.get("ph") == "i" for e in events)


def test_trace_span_exception_still_recorded(tmp_path):
    path = str(tmp_path / "trace.json")
    tracer = trace_mod.Tracer(path)
    with pytest.raises(RuntimeError):
        with tracer.span("boom"):
            raise RuntimeError("x")
    tracer.close()
    doc = json.loads(open(path).read())
    assert [e["name"] for e in doc["traceEvents"]] == ["boom"]


class _HandClock:
    """A clock the test sets: every read returns ``now``."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def test_span_self_time_by_hand_set_clock(tmp_path):
    """Self time = a span's duration minus what its CHILD spans covered
    (grandchildren count through their parent); a ``complete`` record is
    nobody's child. Worked by hand on a clock the test sets."""
    clock = _HandClock()
    tracer = trace_mod.Tracer(
        str(tmp_path / "trace.json"), clock=clock
    )
    seen = []
    tracer.listeners.append(
        lambda name, dur, args, self_s: seen.append((name, dur, self_s))
    )
    with tracer.span("pass"):
        clock.now += 1.0  # pass's own: 1
        with tracer.span("admit"):
            clock.now += 2.0  # admit's own: 2
            with tracer.span("row_alloc"):
                clock.now += 4.0
            # A request-level record ending inside admit: 5 s long,
            # begun before admit did; it is taken from nobody.
            tracer.complete("req_queue", 5.0, rid=7)
        clock.now += 8.0  # pass's own: 1 + 8
        with tracer.span("emit"):
            clock.now += 16.0
    got = {name: (round(dur, 9), round(self_s, 9)) for name, dur, self_s in seen}
    assert got == {
        "row_alloc": (4.0, 4.0),
        "req_queue": (5.0, 5.0),
        "admit": (6.0, 2.0),
        "emit": (16.0, 16.0),
        "pass": (31.0, 9.0),
    }
    # Self times of a tree of spans partition the root's duration.
    assert sum(s for n, _, s in seen if n != "req_queue") == pytest.approx(31.0)
    tracer.close()
    doc = json.loads(open(tracer.path).read())
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["pass"]["dur"] == 31e6 and by_name["pass"]["self_dur"] == 9e6
    assert by_name["admit"]["self_dur"] == 2e6
    assert by_name["req_queue"]["args"] == {"rid": 7}


def test_self_time_is_per_thread(tmp_path):
    """A span open on another thread is no parent: each thread has its
    own stack of open spans."""
    tracer = trace_mod.Tracer(None)
    seen = []
    tracer.listeners.append(
        lambda name, dur, args, self_s: seen.append((name, dur, self_s))
    )
    inside = threading.Event()
    leave = threading.Event()

    def other():
        with tracer.span("other"):
            inside.set()
            leave.wait(timeout=30)

    t = threading.Thread(target=other)
    with tracer.span("mine"):
        t.start()
        assert inside.wait(timeout=30)
        assert sorted(
            name for stack in tracer.live_spans().values() for name, _ in stack
        ) == ["mine", "other"]
    leave.set()
    t.join(timeout=30)
    assert not t.is_alive()
    for name, dur, self_s in seen:
        assert self_s == dur, name  # neither was the other's child
    assert tracer.live_spans() == {}


def test_unbuffered_tracer_writes_nothing_and_still_feeds_listeners(tmp_path):
    """``Tracer(None)``, what the serve scheduler holds when no
    telemetry dir is set: spans reach the listeners (and the profiler),
    nothing is buffered, close writes no file."""
    tracer = trace_mod.Tracer(None, annotate=trace_mod.jax_annotation())
    assert tracer.enabled and tracer.path is None
    seen = []
    tracer.listeners.append(lambda *a: seen.append(a[0]))
    with tracer.span("a", k=1) as sp:
        sp.args["late"] = 2  # an outcome known only at exit
    tracer.complete("b", 0.5)
    tracer.instant("c")
    assert seen == ["a", "b"]
    assert tracer._events == []
    tracer.close()
    assert list(tmp_path.iterdir()) == []
    with tracer.span("after_close"):
        pass
    assert seen == ["a", "b"]  # a closed tracer tells nobody


def test_span_is_a_host_event_in_the_profilers_xplane(tmp_path):
    """While a profiler session runs, a span is an event of the same
    name (its args as the event's stats) in a host plane of the xplane:
    the program's phases on the device operations' clock."""
    import glob

    import jax
    import jax.numpy as jnp

    try:
        from jax.profiler import ProfileData
    except ImportError:
        pytest.skip("this jax has no jax.profiler.ProfileData")
    tracer = trace_mod.Tracer(None, annotate=trace_mod.jax_annotation())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("tpufw_test_phase", rows=3):
            jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    assert paths, "the profiler wrote no xplane"
    hits = [
        (plane.name, dict(ev.stats), ev.duration_ns)
        for plane in ProfileData.from_file(paths[-1]).planes
        for line in plane.lines
        for ev in line.events
        if ev.name == "tpufw_test_phase"
    ]
    assert len(hits) == 1, hits
    plane, stats, dur_ns = hits[0]
    assert plane.startswith("/host:")
    assert stats.get("rows") == 3 and dur_ns > 0


def test_a_tracer_without_an_annotation_factory_imports_no_jax(tmp_path):
    """The router and the load tools trace with telemetry on and have
    no device: building a tracer and running spans through it must not
    import jax. Only a caller that hands ``jax_annotation()`` in pays
    for the profiler."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from tpufw.obs import trace\n"
        f"t = trace.Tracer({str(tmp_path / 'trace-router.json')!r})\n"
        "with t.span('route', replica=1):\n"
        "    pass\n"
        "t.close()\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr
    doc = json.loads((tmp_path / "trace-router.json").read_text())
    assert [e["name"] for e in doc["traceEvents"]] == ["route"]


def test_null_tracer_shares_one_context_manager():
    t = trace_mod.NULL
    assert t.span("a") is t.span("b")  # no per-call allocation
    with t.span("a"):
        pass
    t.complete("x", 1.0)
    t.close()


# -------------------------------------------------------------------- skew


def _fake_gather(rows):
    return lambda local: rows


def test_straggler_detected_on_synthetic_skew(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = events_mod.EventLog(path)
    reg = Registry()
    mon = SkewMonitor(
        registry=reg,
        events=log,
        factor=2.0,
        gather=_fake_gather(
            [(1.0, 0.1), (1.1, 0.1), (2.5, 1.4), (0.9, 0.1)]
        ),
    )
    stragglers = mon.record(step=8, window_time_s=1.0, data_wait_s=0.1)
    log.close()
    assert stragglers == [2]
    events = events_mod.read_events(path)
    assert len(events) == 1
    ev = events[0]
    events_mod.validate(ev)
    assert ev["kind"] == "straggler_detected"
    assert ev["level"] == "warn"
    assert ev["straggler_hosts"] == [2]
    assert ev["step"] == 8
    assert ev["median_s"] == pytest.approx(1.05)
    # Per-host gauges published for every host, not just stragglers.
    text = reg.render()
    for h in range(4):
        assert f'tpufw_train_host_window_seconds{{host="{h}"}}' in text
    assert 'tpufw_train_host_data_wait_seconds{host="2"} 1.4' in text
    assert "tpufw_train_stragglers_total 1" in text


def test_no_straggler_on_healthy_fleet(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = events_mod.EventLog(path)
    mon = SkewMonitor(
        events=log,
        factor=2.0,
        gather=_fake_gather([(1.0, 0.1), (1.05, 0.1), (0.98, 0.1)]),
    )
    assert mon.record(1, 1.0, 0.1) == []
    log.close()
    assert events_mod.read_events(path) == []


def test_tiny_window_noise_not_flagged():
    # 2x the median but only 20ms over it: min_gap_s suppresses the
    # scheduler-noise false positive a CPU smoke run would hit.
    mon = SkewMonitor(
        factor=2.0,
        min_gap_s=0.05,
        gather=_fake_gather([(0.010, 0.0), (0.025, 0.0), (0.012, 0.0)]),
    )
    assert mon.record(1, 0.01, 0.0) == []


def test_single_host_never_straggles():
    mon = SkewMonitor(gather=_fake_gather([(5.0, 1.0)]))
    assert mon.record(1, 5.0, 1.0) == []


def test_skew_factor_validation():
    with pytest.raises(ValueError):
        SkewMonitor(factor=1.0)


# ------------------------------------------------------------------- Meter


def test_meter_publishes_histograms_and_gauges():
    from tpufw.train.metrics import Meter

    reg = Registry()
    meter = Meter(
        tokens_per_step=1000,
        flops_per_token=6e9,
        n_chips=4,
        registry=reg,
    )
    meter.start()
    time.sleep(0.01)
    # A 4-step window with 0.08s of summed data wait.
    meter.stop(4, 2.5, data_wait_s=0.08, n_steps=4)
    text = reg.render()
    assert "tpufw_train_steps_total 4" in text
    assert "tpufw_train_tokens_total 4000" in text
    assert "tpufw_train_step 4" in text
    assert "tpufw_train_loss 2.5" in text
    # data_wait histogram: 4 observations of the 0.02 per-step average,
    # summing back to the window's 0.08 total.
    h = reg.histogram("tpufw_train_data_wait_seconds")
    assert h.value() == 4
    assert "tpufw_train_data_wait_seconds_sum 0.08" in text
    assert reg.histogram("tpufw_train_step_time_seconds").value() == 4


def test_meter_without_registry_unchanged():
    from tpufw.train.metrics import Meter

    meter = Meter(tokens_per_step=10, flops_per_token=1.0, n_chips=1)
    meter.start()
    sm = meter.stop(1, 1.0)
    assert sm.step == 1 and meter.registry is None


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


def test_detect_chip_knows_the_chip_and_refuses_to_guess():
    from tpufw.utils.hardware import CHIP_SPECS, detect_chip

    # "TPU v5 lite" is what jax 0.9.0 / libtpu 0.0.34 report for a v5e
    # (chip_smoke.py's device line on the chip tool's machine, PR 21).
    for kind in ("TPU v5 lite", "TPU v5e", "TPU v5litepod"):
        assert detect_chip(_FakeDevice("tpu", kind)) is CHIP_SPECS["v5e"]
    assert detect_chip(_FakeDevice("tpu", "TPU v4")) is CHIP_SPECS["v4"]
    # An accelerator the table does not know is an error, not a v5e.
    with pytest.raises(ValueError, match="TPU v9 mega"):
        detect_chip(_FakeDevice("tpu", "TPU v9 mega"))
    with pytest.raises(ValueError, match="unknown accelerator"):
        detect_chip(_FakeDevice("gpu", "NVIDIA H100"))
    # A CPU has no row at all — no invented peak.
    assert detect_chip(_FakeDevice("cpu", "cpu")) is None
    assert "cpu" not in CHIP_SPECS


def test_cpu_meter_emits_no_mfu():
    from tpufw.train.metrics import Meter
    from tpufw.utils.hardware import CHIP_SPECS

    reg = Registry()
    meter = Meter(
        tokens_per_step=1000, flops_per_token=6e9, n_chips=1, registry=reg
    )  # the suite runs on CPU devices: no chip detected
    assert meter.chip is None
    meter.start()
    sm = meter.stop(1, 2.5)
    assert sm.mfu is None
    assert "mfu" not in sm.as_dict() and "mfu" not in sm.event_fields()
    assert sm.tokens_per_sec_per_chip > 0
    assert "tpufw_train_mfu" not in reg.render()
    # With a chip there is a peak to divide by, and the MFU appears.
    reg = Registry()
    meter = Meter(
        tokens_per_step=1000, flops_per_token=6e9, n_chips=1,
        chip=CHIP_SPECS["v5e"], registry=reg,
    )
    meter.start()
    sm = meter.stop(1, 2.5)
    assert sm.mfu > 0 and "mfu" in sm.as_dict()
    assert "tpufw_train_mfu " in reg.render()


# ------------------------------------------------- disabled-overhead budget


def test_disabled_telemetry_per_step_overhead_below_1pct():
    """Acceptance: with observability off, per-step overhead < 1%.

    One loop iteration's worth of disabled-telemetry calls (the
    data_fetch complete + step_dispatch/host_sync-shaped spans + a step
    event + the skew guard + the watchdog arm/disarm pair + a goodput
    add) must cost well under 1% of a step. The
    repo's smallest real steps are ~25 ms (llama3_tiny on the CPU
    mesh); 1% of that is 250 us. Budget 100 us per step — an order of
    magnitude above the measured no-op cost (~2-5 us), two orders
    below the step."""
    tel = Telemetry.disabled()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        tel.tracer.complete("data_fetch", 0.001)
        tel.watchdog.arm()
        with tel.tracer.span("step_dispatch"):
            pass
        with tel.tracer.span("host_sync"):
            tel.events.emit(
                "step", step=1, loss=1.0, step_time_s=0.1, data_wait_s=0.0
            )
            if tel.skew is not None:
                tel.skew.record(1, 0.1, 0.0)
        tel.watchdog.disarm()
        tel.goodput.add("productive", 0.001)
        with tel.tracer.span("eval"):
            pass
        with tel.tracer.span("checkpoint"):
            pass
    per_step = (time.perf_counter() - t0) / n
    assert per_step < 100e-6, f"disabled telemetry {per_step*1e6:.1f}us/step"


@pytest.mark.parametrize("ledger_on", [False, True], ids=["tracer", "pass_ledger"])
def test_live_unbuffered_tracer_per_pass_overhead_below_1pct(ledger_on):
    """The serve scheduler's tracer is never the null one: without a
    telemetry dir it is ``Tracer(None, annotate=jax_annotation())``,
    which still enters a profiler annotation (no session: a flag test),
    keeps the open-span stack and feeds the phase counter. One scheduler
    pass's worth of it — the nine spans of an admission pass, nested as
    ``_SlotScheduler`` nests them, a counter listener attached — must
    cost under 1% of a 25 ms pass: 250 us. That is the repo's smallest
    real step, the one the disabled budget above is held to, and below
    any pass of the benchmark's cells (a decode chunk there is 5-20 ms a
    token times 8 or 16). The ceiling is four to six times the 42 us
    this box measures (63 us with the ledger), and the reading is the
    best of five batches: a loaded machine must not read as a slow
    tracer. What cannot drift with the machine is counted instead: one
    clock read at each end of a span and one listener call a span,
    nothing buffered.

    ``pass_ledger``: the same pass with the scheduler's ledger of passes
    on (``serve._PassLedger``: a second listener, the pools'
    ``dispatched`` hook at the return of each program's call,
    ``end_pass`` where the pass ends), on the same budget. What it adds
    is counted too: no span of its own, and one clock read at each close
    of a phase while the device is drained (admit, the fetch, the decode
    chunk's own end, the emit), one at the dispatch that feeds it again,
    one where the wait ends and one where the pass does."""
    from tpufw.workloads import serve as serve_mod

    reads = [0]

    def clock():
        reads[0] += 1
        return time.perf_counter()

    tracer = trace_mod.Tracer(
        None, annotate=trace_mod.jax_annotation(), clock=clock
    )
    reg = Registry()
    phase_s = reg.counter("tpufw_serve_phase_seconds_total")
    calls = [0]

    def on_span(name, dur, args, self_s):
        calls[0] += 1
        phase_s.inc(self_s, phase=name)

    tracer.listeners.append(on_span)
    ledger = (
        serve_mod._PassLedger(tracer, reg, clock=clock) if ledger_on else None
    )

    def fed(what):
        if ledger is not None:
            ledger.fed(what)

    def one_pass():
        with tracer.span("serve_admit", queued=1) as sp:
            sp.args["admitted"] = 1
        with tracer.span(
            "serve_prefill_chunk", slot=0, cursor=0, prompt=256,
            width=256, final=False,
        ):
            with tracer.span("serve_row_alloc", shared_pages=0):
                fed("row")
            fed("chunk")
        with tracer.span("serve_emit", slot=0):
            pass
        with tracer.span(
            "serve_decode_chunk", k=16, rows=4, ahead=2, key_rung=2048,
            row_rung=8,
        ):
            with tracer.span("serve_decode_dispatch"):
                fed("decode")
            with tracer.span("serve_device_wait", **{"for": "decode"}):
                pass
            with tracer.span("serve_fetch"):
                pass
        with tracer.span("serve_emit", rows=4):
            pass
        if ledger is not None:
            ledger.end_pass()

    one_pass()  # leaves the device drained, as every later pass finds it
    reads[0] = calls[0] = 0
    one_pass()
    # The ledger's seven: admit's close, the zero-fill's return, the
    # wait's end, the closes of the fetch, the decode chunk and the emit,
    # the pass's end.
    assert (reads[0], calls[0]) == (18 + 7 * ledger_on, 9)
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(400):
            one_pass()
        best = min(best, (time.perf_counter() - t0) / 400)
    assert best < 250e-6, f"live unbuffered tracer {best*1e6:.1f}us/pass"
    assert tracer._events == [] and phase_s.value(phase="serve_emit") > 0
    if ledger_on:
        passes = reg.counter("tpufw_serve_pass_seconds_total")
        assert passes.value(**{"pass": "decode_behind_prefill"}) > 0
        assert passes.value(**{"pass": "decode"}) == 0
        steps = reg.counter("tpufw_serve_pass_steps_total")
        assert steps.value(**{"pass": "decode_behind_prefill"}) == 16 * 2002


def test_disabled_telemetry_is_shared_and_inert(tmp_path):
    tel = Telemetry.disabled()
    assert tel is Telemetry.disabled()  # one shared instance
    assert not tel.enabled
    assert tel.registry is None and tel.skew is None
    tel.close()  # must not poison later users
    assert Telemetry.create() is tel  # all-None knobs -> disabled


# --------------------------------------------- end-to-end trainer smoke


@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory):
    """One tiny CPU training run with full telemetry: metrics port,
    events, trace. Scrapes /metrics DURING the run (from on_metrics,
    i.e. between sync windows) — the acceptance criterion is that a
    live run serves Prometheus text, not that the file outlives it."""
    import itertools

    from tpufw.mesh import MeshConfig
    from tpufw.models import LLAMA_CONFIGS, Llama
    from tpufw.train import Trainer, TrainerConfig, synthetic_batches

    tiny = LLAMA_CONFIGS["llama3_tiny"]
    out = tmp_path_factory.mktemp("telemetry")
    cfg = TrainerConfig(
        batch_size=8,
        seq_len=17,
        total_steps=6,
        lr=1e-3,
        warmup_steps=2,
        sync_every=2,
        telemetry_dir=str(out),
        metrics_port=0,
    )
    trainer = Trainer(Llama(tiny), cfg, MeshConfig(data=8))
    trainer.init_state()
    batch = next(synthetic_batches(8, 17, tiny.vocab_size, seed=0))
    scraped = {}

    def on_metrics(_m):
        if "text" in scraped:
            return
        port = trainer.telemetry.bound_port
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as resp:
            scraped["text"] = resp.read().decode()

    history = trainer.run(
        itertools.repeat(batch, 6),
        model_flops_per_token=tiny.flops_per_token(16),
        on_metrics=on_metrics,
    )
    return trainer, history, out, scraped


def test_live_scrape_has_step_mfu_data_wait(telemetry_run):
    _, _, _, scraped = telemetry_run
    text = scraped["text"]
    assert "# TYPE tpufw_train_steps_total counter" in text
    assert "tpufw_train_tokens_per_sec_per_chip " in text
    # A CPU run has no peak to take a utilization against.
    assert "tpufw_train_mfu" not in text
    # Run identity published at startup: every scrape is joinable to a
    # build/backend/mesh/model, not just the final snapshot.
    info_lines = [
        ln for ln in text.splitlines()
        if ln.startswith("tpufw_run_info{")
    ]
    assert len(info_lines) == 1
    assert 'backend="cpu"' in info_lines[0]
    assert 'model="Llama"' in info_lines[0]
    assert "jax_version=" in info_lines[0]
    assert info_lines[0].endswith(" 1")
    assert "tpufw_train_data_wait_seconds_bucket" in text
    assert "tpufw_train_step_time_seconds_count" in text
    # At least the first sync window (step 1) had published.
    steps_line = [
        ln
        for ln in text.splitlines()
        if ln.startswith("tpufw_train_steps_total ")
    ][0]
    assert float(steps_line.split()[-1]) >= 1


def test_events_jsonl_schema_valid(telemetry_run):
    _, history, out, _ = telemetry_run
    events = events_mod.read_events(str(out / "events.jsonl"))
    for ev in events:
        events_mod.validate(ev)
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "run_start"
    # run_end closes the run; the goodput rollup rides the telemetry
    # close after it, as the final line.
    assert kinds[-2:] == ["run_end", "goodput"]
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == len(history)
    assert steps[-1]["step"] == history[-1].step
    assert steps[-1]["loss"] == pytest.approx(history[-1].loss, rel=1e-4)


def test_metrics_prom_snapshot_written(telemetry_run):
    _, _, out, _ = telemetry_run
    text = (out / "metrics.prom").read_text()
    assert "tpufw_train_steps_total 6" in text


def test_goodput_rollup_accounts_for_wallclock(telemetry_run):
    """Acceptance: the per-run goodput.json's categories sum to the
    run's wall-clock within 2%, with real productive time booked from
    the step spans, and the headline metrics land in the final
    snapshot."""
    _, _, out, _ = telemetry_run
    gp = json.loads((out / "goodput.json").read_text())
    wall = gp["wall_s"]
    total = sum(gp["categories"].values())
    assert wall > 0
    assert abs(total - wall) <= 0.02 * wall
    assert gp["categories"]["productive"] > 0
    assert 0 < gp["goodput_ratio"] <= 1
    assert gp["replay_until_step"] == 0  # fresh run: nothing replayed
    text = (out / "metrics.prom").read_text()
    assert "tpufw_goodput_ratio " in text
    assert 'tpufw_badput_seconds_total{category="idle"}' in text
    # The goodput event closed out the event log, schema-valid.
    events = events_mod.read_events(str(out / "events.jsonl"))
    goodputs = [e for e in events if e["kind"] == "goodput"]
    assert len(goodputs) == 1
    events_mod.validate(goodputs[0])
    assert goodputs[0]["goodput_ratio"] == gp["goodput_ratio"]


def test_crash_bundle_absent_on_clean_run(telemetry_run):
    """A clean exit must not cry wolf: no bundle, no hang dumps, no
    leftover empty fault log."""
    _, _, out, _ = telemetry_run
    assert not list(out.glob("crash-bundle-*"))
    assert not list(out.glob("hang-*.json"))
    assert not list(out.glob("fault-*.log"))


def test_trace_spans_cover_step_loop_wallclock(telemetry_run):
    """Acceptance: spans cover >= 95% of wall-clock between the first
    and last step. Window = start of the first step_dispatch span to
    the end of the last host_sync span; coverage = merged union of all
    complete-event intervals inside it."""
    _, _, out, _ = telemetry_run
    doc = json.loads((out / "trace.json").read_text())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {s["name"] for s in spans} >= {
        "data_fetch",
        "step_dispatch",
        "host_sync",
    }
    t0 = min(
        s["ts"] for s in spans if s["name"] == "step_dispatch"
    )
    t1 = max(
        s["ts"] + s["dur"] for s in spans if s["name"] == "host_sync"
    )
    ivals = sorted(
        (max(s["ts"], t0), min(s["ts"] + s["dur"], t1))
        for s in spans
        if s["ts"] + s["dur"] > t0 and s["ts"] < t1
    )
    covered, cur0, cur1 = 0.0, None, None
    for a, b in ivals:
        if cur1 is None or a > cur1:
            if cur1 is not None:
                covered += cur1 - cur0
            cur0, cur1 = a, b
        else:
            cur1 = max(cur1, b)
    if cur1 is not None:
        covered += cur1 - cur0
    assert covered / (t1 - t0) >= 0.95, (
        f"spans cover {covered / (t1 - t0):.1%} of the step loop"
    )


def test_telemetry_closed_after_run(telemetry_run):
    trainer, _, _, _ = telemetry_run
    tel = trainer.telemetry
    # Server is down (close() shut it down); scrape must now fail.
    with pytest.raises(Exception):
        urllib.request.urlopen(
            f"http://127.0.0.1:{tel.bound_port}/metrics", timeout=2
        )
