"""Span tracing with Chrome trace-event JSON export.

Context-manager spans around the trainer's phases (data-fetch,
step-dispatch, host-sync, checkpoint, tune-candidate) collected
in-memory and dumped as Chrome trace-event JSON (the ``traceEvents``
``"ph": "X"`` complete-event form) on close — drag the file into
https://ui.perfetto.dev or chrome://tracing and the step loop reads
like a flame chart. This is the microscope for WHERE a window's time
went; XProf (``utils/profiling.py``) stays the microscope for what
the devices did inside the step.

One span, three sinks. Besides the JSON buffer a span (1) enters a
``jax.profiler.TraceAnnotation`` of its name, so that while a profiler
session runs the program's spans are host events in the same xplane,
on the same clock, as the device operations (outside a session the
annotation is a flag test); and (2) is handed to ``listeners`` with its
duration and its SELF time (duration minus what its child spans
covered), which is what counters sum without double-booking a nested
span. The annotation factory is the caller's to hand in (``annotate=
jax_annotation()``): obs imports no jax, and a role without a device
pays nothing for it. ``Tracer(None)`` is the live-but-unbuffered form:
it annotates and feeds listeners, keeps no events and writes no file.

Disabled tracing must be free enough to leave the instrumentation
in the loop unconditionally: ``NullTracer.span`` returns one shared
no-op context manager — no allocation, no clock read (the <1%
per-step overhead budget is asserted in tests/test_obs.py, for the
unbuffered tracer too).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import List, Optional


def jax_annotation():
    """``jax.profiler.TraceAnnotation``, or None where jax is not
    installed: what a process whose spans can reach a device xplane
    (the serve scheduler, the trainer) hands a ``Tracer`` as
    ``annotate``. Never called by obs itself, so stdlib-only roles
    (the router, the load tools) trace without importing jax."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # noqa: BLE001 — no jax, or a jax without it
        return None
    return TraceAnnotation


class _Span:
    """Reusable-shape span context manager; one allocation per enter
    (cheap relative to the phases traced, which are >=100us). ``args``
    may be added to until exit (an outcome known only at the end); the
    profiler annotation carries them as they were on entry."""

    __slots__ = ("_tracer", "name", "args", "_frame", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        ann = self._tracer._annotate
        if ann is not None:
            ann = ann(self.name, **self.args)
            ann.__enter__()
        self._ann = ann
        self._frame = self._tracer._push(self.name)
        return self

    def __exit__(self, *exc):
        self._tracer._pop(self._frame, self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Collects complete events; ``close()`` writes Perfetto-loadable
    JSON. Timestamps are microseconds on the process-local
    ``perf_counter`` clock (Chrome trace epochs are arbitrary); the
    wall-clock anchor is recorded in ``otherData`` for cross-host
    alignment. ``path=None`` buffers no event and writes no file; the
    profiler annotations and the listeners work the same. ``annotate``
    is a context-manager factory ``(name, **args)`` entered around every
    span, ``jax_annotation()`` in a process with a device; ``clock`` is
    for tests that set the time by hand."""

    def __init__(
        self,
        path: Optional[str],
        pid: int = 0,
        process_name: str = "",
        max_events: Optional[int] = None,
        clock=time.perf_counter,
        annotate=None,
    ):
        self.path = path
        self.pid = pid
        self._name = process_name
        self._clock = clock
        self._annotate = annotate
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._t0 = clock()
        self._wall0 = time.time()
        self._closed = False
        # Long-running processes (the serving scheduler) trace hot
        # per-chunk spans forever: cap the buffer so memory stays
        # bounded — the trace keeps the RUN'S HEAD (startup + first
        # traffic, where compile stalls and admission bugs live) and
        # counts what it dropped.
        self._max = max_events
        self._dropped = 0
        # Observers called (name, dur_s, args, self_s) after each
        # complete span — the goodput ledger and the serve scheduler's
        # phase counter ride these instead of re-timing the loop.
        # Wiring-time mutation only.
        self.listeners: List = []
        # Open spans per thread, innermost last, as [name, start,
        # seconds its closed children covered]: the hang watchdog's
        # "where was the run wedged" dump, and what self time is
        # computed from.
        self._live: dict = {}

    enabled = True

    def _ts(self, t: float) -> float:
        return round((t - self._t0) * 1e6, 3)

    def _push(self, name: str) -> list:
        frame = [name, self._clock(), 0.0]
        tid = threading.get_ident()
        with self._lock:
            self._live.setdefault(tid, []).append(frame)
        return frame

    def _pop(self, frame: list, args: Optional[dict]) -> None:
        name, t0, child_s = frame
        t1 = self._clock()
        tid = threading.get_ident()
        with self._lock:
            stack = self._live.get(tid)
            if stack and stack[-1] is frame:
                stack.pop()
                if stack:
                    stack[-1][2] += t1 - t0
                else:
                    del self._live[tid]
            if self._closed:
                return
            self._buffer_complete(name, t0, t1, args, t1 - t0 - child_s)
        self._notify(name, t1 - t0, args, t1 - t0 - child_s)

    def live_spans(self) -> dict:
        """Snapshot of currently-open spans: thread ident ->
        [(name, open_for_s), ...] innermost last. The watchdog dumps
        this so a hang report names the wedged phase, not just the
        wedged line."""
        now = self._clock()
        with self._lock:
            return {
                tid: [(name, round(now - t0, 3)) for name, t0, _ in stack]
                for tid, stack in self._live.items()
            }

    def open_name(self) -> Optional[str]:
        """Name of the innermost span open on the CALLING thread, None
        where it has none open: what a listener that is also told of
        moments inside a span (the serve scheduler's pass ledger, at the
        return of a dispatch) books that moment under. No clock read,
        no lock: a thread's stack is only ever changed by that thread."""
        stack = self._live.get(threading.get_ident())
        return stack[-1][0] if stack else None

    def _buffer_complete(
        self, name: str, t0: float, t1: float, args, self_s: float
    ) -> None:
        """Append one complete event; the caller holds the lock."""
        if self.path is None:
            return
        if self._max is not None and len(self._events) >= self._max:
            self._dropped += 1
            return
        ev = {
            "name": name,
            "ph": "X",
            "ts": self._ts(t0),
            "dur": round((t1 - t0) * 1e6, 3),
            # Not a trace-event field (viewers ignore it): the span's
            # duration less what its child spans covered, microseconds.
            "self_dur": round(self_s * 1e6, 3),
            "pid": self.pid,
            "tid": threading.get_ident() & 0xFFFF,
        }
        if args:
            ev["args"] = args
        self._events.append(ev)

    def _notify(self, name: str, dur_s: float, args, self_s: float) -> None:
        # Listeners fire even past the buffer cap (ledger accounting
        # must not stop when the trace fills) and outside the lock.
        for fn in tuple(self.listeners):
            try:
                fn(name, dur_s, args or None, self_s)
            except Exception:
                pass  # observability must never take down the run

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def complete(self, name: str, dur_s: float, **args) -> None:
        """Record a span that just ENDED, ``dur_s`` long — for phases
        whose duration is measured elsewhere (e.g. ``timed_batches``
        already times the data wait; re-timing it would double-count
        the clock reads). It is no child of whatever span is open:
        what it covers may have begun before that span did."""
        t1 = self._clock()
        with self._lock:
            if self._closed:
                return
            self._buffer_complete(name, t1 - dur_s, t1, args, dur_s)
        self._notify(name, dur_s, args, dur_s)

    def instant(self, name: str, **args) -> None:
        if self.path is None:
            return  # a marker has no duration: nothing for a listener
        ev = {
            "name": name,
            "ph": "i",
            "s": "p",
            "ts": self._ts(self._clock()),
            "pid": self.pid,
            "tid": threading.get_ident() & 0xFFFF,
        }
        if args:
            ev["args"] = args
        with self._lock:
            if self._closed:
                return
            if self._max is not None and len(self._events) >= self._max:
                self._dropped += 1
                return
            self._events.append(ev)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            events = self._events
        if self.path is None:
            return
        if self._name:
            events = [
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": self.pid,
                    "args": {"name": self._name},
                }
            ] + events
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "wall_epoch_s": self._wall0,
                "dropped_events": self._dropped,
            },
        }
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, self.path)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class NullTracer:
    """Disabled stand-in. ``span`` hands back one shared no-op context
    manager — the hot-loop cost of leaving spans in place is two
    attribute lookups and a call."""

    path = None
    enabled = False

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def complete(self, name: str, dur_s: float, **args) -> None:
        pass

    def instant(self, name: str, **args) -> None:
        pass

    def live_spans(self) -> dict:
        return {}

    def open_name(self) -> None:
        return None

    def close(self) -> None:
        pass


NULL = NullTracer()
