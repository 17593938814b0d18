"""Compiled-program cost observatory: FLOPs/bytes/HBM per executable,
live MFU + roofline attribution, and the on-demand profiler hooks.

The goodput ledger attributes *seconds* to categories and the tracer
attributes them to spans; this module attributes them to *hardware* —
for every program the run compiles (train step, pipeline step, tune
trials, serve decode chunks, paged inserts) it harvests XLA's own
``cost_analysis()`` (FLOPs, bytes accessed) and ``memory_analysis()``
(argument/output/temp HBM) through the jit AOT path, writes the table
to ``<telemetry_dir>/programs.json``, and combines the static costs
with the measured wall-clock the trainers/scheduler already collect to
publish ``tpufw_program_mfu`` / ``tpufw_program_ai`` /
``tpufw_program_compute_bound`` / ``tpufw_hbm_headroom_bytes``.

Harvest is observe-only: ``observe_jit`` lowers and AOT-compiles the
SAME ``jax.jit`` object the caller is about to execute. Lowering is
abstract (no donated buffer is consumed) and each program is harvested
once per name, so the steady-state cost is one dict lookup; the one
extra executable build per unique program is absorbed by the
persistent XLA compile cache when enabled. ``TPUFW_PERF_OBS=0`` turns
the whole observatory off (the null object keeps every probe site
branch-free, same discipline as the rest of tpufw.obs).

Cost figures are PER DEVICE: the compiled module XLA reports on is
the SPMD-partitioned per-device program, so MFU divides by one chip's
peak and HBM headroom compares against one chip's capacity.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

from tpufw.obs import roofline as roofline_mod

PROGRAMS_FILENAME = "programs.json"


def _cost_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict — empty on
    backends without an HLO cost model."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — unimplemented on some backends
        return {}
    return dict(ca or {})


def _memory_dict(compiled) -> dict:
    """``Compiled.memory_analysis()`` attributes as a plain dict of
    byte counts (empty when the backend does not implement it)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return {}
    if ma is None:
        return {}
    out = {}
    for field, key in (
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("temp_size_in_bytes", "temp_bytes"),
        ("alias_size_in_bytes", "alias_bytes"),
        ("generated_code_size_in_bytes", "generated_code_bytes"),
    ):
        v = getattr(ma, field, None)
        if v is not None:
            out[key] = int(v)
    return out


def parse_profile_steps(raw: str) -> Optional[Tuple[int, int]]:
    """``TPUFW_PROFILE_STEPS=a:b`` -> (a, b), or None when unset or
    malformed (a bad value must never kill a training run)."""
    raw = (raw or "").strip()
    if not raw:
        return None
    parts = raw.split(":")
    if len(parts) != 2:
        return None
    try:
        start, stop = int(parts[0]), int(parts[1])
    except ValueError:
        return None
    if start < 0 or stop <= start:
        return None
    return start, stop


def resolve_profile_window(
    profile_dir: Optional[str],
    profile_start: int,
    profile_stop: int,
    telemetry_dir: Optional[str] = None,
) -> Tuple[Optional[str], int, int]:
    """The StepProfiler knobs after the ``TPUFW_PROFILE_STEPS`` env
    override: the env window wins over the config window, and when no
    profile dir is configured the capture lands under the telemetry
    dir (``<telemetry_dir>/xprof``) so the trace is linkable from the
    run's own artifact directory."""
    from tpufw.workloads.env import env_str

    window = parse_profile_steps(env_str("profile_steps", ""))
    if window is None:
        return profile_dir, profile_start, profile_stop
    out_dir = profile_dir or (
        os.path.join(telemetry_dir, "xprof") if telemetry_dir else None
    )
    return out_dir, window[0], window[1]


class ProfileTrigger:
    """On-demand ``jax.profiler`` capture behind ``/debug/profile``:
    one time-bounded trace at a time, taken on a daemon thread so the
    HTTP handler returns immediately with the trace path."""

    def __init__(self, out_dir: str, max_seconds: float = 60.0):
        self.out_dir = out_dir
        self.max_seconds = max_seconds
        self._lock = threading.Lock()
        self._active = False

    def trigger(self, seconds: float = 2.0) -> dict:
        seconds = min(max(float(seconds), 0.1), self.max_seconds)
        with self._lock:
            if self._active:
                return {"error": "capture already in progress"}
            self._active = True
        trace_dir = os.path.join(
            self.out_dir, f"ondemand-{int(time.time())}"
        )

        def capture():
            try:
                import jax

                os.makedirs(trace_dir, exist_ok=True)
                jax.profiler.start_trace(trace_dir)
                time.sleep(seconds)
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001 — never kill the server
                pass
            finally:
                with self._lock:
                    self._active = False

        threading.Thread(
            target=capture, daemon=True, name="obs-profile-capture"
        ).start()
        return {"started": True, "dir": trace_dir, "seconds": seconds}


class PerfObservatory:
    """Per-run registry of compiled-program costs + live roofline
    gauges. ``registry``/``out_dir`` may each be None (gauges only, or
    file only); ``peaks`` defaults to the detected chip's row with the
    ``TPUFW_PEAK_*`` overrides applied."""

    enabled = True

    def __init__(
        self,
        registry=None,
        out_dir: Optional[str] = None,
        peaks: Optional[roofline_mod.PeakSpec] = None,
        key: Optional[str] = None,
    ):
        self._registry = registry
        self._out_dir = out_dir
        self._peaks = peaks
        self._key = key
        self._lock = threading.Lock()
        self._programs: Dict[str, Dict[str, Any]] = {}
        self._closed = False

    # -- static costs -------------------------------------------------

    @property
    def peaks(self) -> roofline_mod.PeakSpec:
        if self._peaks is None:
            self._peaks = roofline_mod.detect_peaks()
        return self._peaks

    def set_key(self, key: str) -> None:
        """Attach the tune-winner-cache-style run key (the trainers
        know it only after the mesh/model resolve)."""
        self._key = key
        self._write()

    def observe_jit(self, name: str, jit_fn, args=(), kwargs=None):
        """Harvest ``jit_fn``'s compiled costs under ``name`` — once;
        repeat calls with a seen name are a dict lookup. Never raises:
        a failed harvest records the error and stops retrying."""
        if name in self._programs:
            return
        try:
            compiled = jit_fn.lower(*args, **(kwargs or {})).compile()
            cost = _cost_dict(compiled)
            mem = _memory_dict(compiled)
        except Exception as e:  # noqa: BLE001 — observe-only, never abort
            with self._lock:
                self._programs.setdefault(
                    name, {"error": f"{type(e).__name__}: {e}"[:300]}
                )
            return
        self.record_costs(
            name,
            flops=float(cost.get("flops", 0.0) or 0.0),
            bytes_accessed=float(cost.get("bytes accessed", 0.0) or 0.0),
            memory=mem,
        )

    def record_costs(
        self,
        name: str,
        flops: float = 0.0,
        bytes_accessed: float = 0.0,
        memory: Optional[dict] = None,
    ) -> None:
        """Ingest one program's static costs (the seam observe_jit
        feeds and tests drive directly) and publish the static gauges."""
        memory = memory or {}
        ai = flops / bytes_accessed if bytes_accessed > 0 else None
        peak_hbm = None
        if memory:
            # Live-at-peak upper bound: arguments + outputs + XLA's
            # own temp high-water mark, minus donated aliases.
            peak_hbm = (
                memory.get("argument_bytes", 0)
                + memory.get("output_bytes", 0)
                + memory.get("temp_bytes", 0)
                - memory.get("alias_bytes", 0)
            )
        entry: Dict[str, Any] = {
            "flops": flops,
            "bytes_accessed": bytes_accessed,
            "ai_flops_per_byte": ai,
            "bound": roofline_mod.classify(ai, self.peaks),
            "peak_hbm_bytes": peak_hbm,
            **memory,
        }
        with self._lock:
            self._programs[name] = entry
        if self._registry is not None:
            if ai is not None:
                self._registry.gauge(
                    "tpufw_program_ai",
                    "arithmetic intensity (FLOPs/byte) of the compiled "
                    "program, from XLA cost_analysis",
                ).set(ai, program=name)
            if entry["bound"] is not None:
                self._registry.gauge(
                    "tpufw_program_compute_bound",
                    "roofline classification: 1 = compute-bound, "
                    "0 = memory-bound (vs the chip balance point)",
                ).set(
                    1 if entry["bound"] == "compute" else 0, program=name
                )
            self._publish_headroom()
        self._write()

    def _publish_headroom(self) -> None:
        """``tpufw_hbm_headroom_bytes`` = chip HBM minus the largest
        per-program peak footprint seen so far (can go negative: that
        IS the OOM warning)."""
        with self._lock:
            peaks_seen = [
                p["peak_hbm_bytes"]
                for p in self._programs.values()
                if p.get("peak_hbm_bytes")
            ]
        if (
            not peaks_seen
            or self._registry is None
            or not self.peaks.hbm_bytes
        ):
            return
        self._registry.gauge(
            "tpufw_hbm_headroom_bytes",
            "per-chip HBM capacity minus the largest compiled-program "
            "peak footprint (negative = expected OOM)",
        ).set(self.peaks.hbm_bytes - max(peaks_seen))

    # -- measured wall ------------------------------------------------

    def record_wall(self, name: str, wall_s: float) -> Optional[float]:
        """Combine a measured per-call wall with the harvested FLOPs
        into MFU for ``name``; returns the MFU (None when the program
        is unknown, has no FLOPs figure, or the wall is degenerate)."""
        if wall_s <= 0:
            return None
        with self._lock:
            entry = self._programs.get(name)
            if entry is None or not entry.get("flops"):
                return None
            entry["wall_s"] = wall_s
            entry["calls"] = entry.get("calls", 0) + 1
            if not self.peaks.flops_per_s:
                return None  # no peak for this device (CPU): no MFU
            mfu = entry["flops"] / (wall_s * self.peaks.flops_per_s)
            entry["mfu"] = mfu
        if self._registry is not None:
            self._registry.gauge(
                "tpufw_program_mfu",
                "measured FLOP utilization of the compiled program: "
                "cost_analysis FLOPs / (wall x per-chip peak FLOPs)",
            ).set(mfu, program=name)
        return mfu

    # -- reads --------------------------------------------------------

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {k: dict(v) for k, v in self._programs.items()}

    def attrib(self, prefix: str = "") -> dict:
        """The bench/goodput summary for programs whose name starts
        with ``prefix``: the highest-FLOP program's last MFU and
        roofline bound, plus the global HBM headroom. Empty dict when
        nothing matched."""
        progs = [
            (n, p)
            for n, p in self.snapshot().items()
            if n.startswith(prefix) and p.get("flops")
        ]
        if not progs:
            return {}
        name, p = max(progs, key=lambda np: np[1]["flops"])
        out: dict = {"program": name}
        if p.get("mfu") is not None:
            out["measured_mfu"] = round(p["mfu"], 4)
        if p.get("bound") is not None:
            out["roofline_bound"] = p["bound"]
        hbm_peaks = [
            q["peak_hbm_bytes"]
            for q in self.snapshot().values()
            if q.get("peak_hbm_bytes")
        ]
        if hbm_peaks and self.peaks.hbm_bytes:
            out["hbm_headroom_bytes"] = int(
                self.peaks.hbm_bytes - max(hbm_peaks)
            )
        return out

    # -- persistence --------------------------------------------------

    def _document(self) -> dict:
        peaks = self.peaks
        with self._lock:
            programs = {k: dict(v) for k, v in self._programs.items()}
        return {
            "version": 1,
            "key": self._key,
            "chip": peaks.chip,
            "peak_flops_per_chip": peaks.flops_per_s,
            "peak_hbm_bw_bytes_per_s": peaks.hbm_bw_bytes_per_s,
            "hbm_bytes_per_chip": peaks.hbm_bytes,
            "balance_flops_per_byte": peaks.balance_flops_per_byte,
            "programs": programs,
        }

    def _write(self) -> None:
        if not self._out_dir:
            return
        path = os.path.join(self._out_dir, PROGRAMS_FILENAME)
        tmp = path + ".tmp"
        try:
            os.makedirs(self._out_dir, exist_ok=True)
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(self._document(), f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass  # telemetry write failure must never abort the run

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._write()


class NullPerfObservatory:
    """Disabled-path twin: every probe is a constant-time no-op (the
    <1% per-step budget asserted in tests/test_perf_obs.py)."""

    enabled = False

    def observe_jit(self, name, jit_fn, args=(), kwargs=None):
        pass

    def record_costs(self, name, flops=0.0, bytes_accessed=0.0,
                     memory=None):
        pass

    def record_wall(self, name, wall_s):
        return None

    def set_key(self, key):
        pass

    def snapshot(self):
        return {}

    def attrib(self, prefix=""):
        return {}

    def close(self):
        pass


NULL = NullPerfObservatory()


def load_programs(telemetry_dir: str) -> Optional[dict]:
    """Read ``<dir>/programs.json``; None when absent or torn (the
    same graceful degradation as the other obs artifacts)."""
    path = os.path.join(telemetry_dir, PROGRAMS_FILENAME)
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError):
        return None
