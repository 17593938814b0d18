"""From the profiler's trace (xplane, read with ``jax.profiler.ProfileData``)
to numbers: device busy and idle time, time per device operation and per
program, and the longest idle gaps with what the host was doing in them.

The reduction works on plain tuples, so the tests feed it a hand-built
trace; only ``load_xplane`` touches jax.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

#: Lines of a device plane. Operations overlap their module's event, so
#: busy time is the union of the operations where the line exists.
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    return hits[-1]


def load_xplane(path: str) -> dict:
    """{plane name: {line name: [(name, start_ns, dur_ns, {})]}}. The
    fourth place was each event's statistics; no reduction reads them and
    a stretch holds a million operations, so they are not fetched."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns), float(ev.duration_ns), {}))
    return out


def union_seconds(intervals) -> float:
    """Total length of the union of (start_ns, dur_ns) intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def gaps(intervals, lo: float, hi: float) -> list:
    """(start_ns, dur_ns) of the idle stretches of [lo, hi), longest first."""
    out, end = [], lo
    for s, d in sorted(intervals):
        if s > end:
            out.append((end, s - end))
        end = max(end, s + d)
    if hi > end:
        out.append((end, hi - end))
    return sorted(out, key=lambda g: -g[1])


def op_label(name: str) -> str:
    """A device operation's event name is its whole HLO line; keep the
    instruction's name, its opcode and its first result shape."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    op = re.search(r"(?:^|[ )}])([a-z][\w\-]*)\(", rest)
    shape = re.search(r"\w+\[[\d,]*\]", rest)
    parts = (head.lstrip("%"), op.group(1) if op else "", shape.group(0) if shape else "")
    return " ".join(x for x in parts if x)[:80]


def module_base(name: str) -> str:
    """``jit__decode_steps_jit(1234)`` -> ``jit__decode_steps_jit``."""
    return re.sub(r"\(\d+\)$", "", name)


def device_planes(planes: dict) -> list:
    return sorted(p for p in planes if p.startswith("/device:TPU"))


def host_events(planes: dict) -> list:
    out = []
    for pname, lines in planes.items():
        if pname.startswith("/host:"):
            for evs in lines.values():
                out.extend(evs)
    return out


def attribute_gap(gap, host) -> str:
    """What the host was doing in an idle gap. Spans nest, and an outer one
    covers whatever its inner ones do, so the answer is the innermost that
    still accounts for the gap: of the host events that cover more than
    half of it, the shortest. Where none does, the event that covers most
    of it; with no event at all, "waiting for a request"."""
    gs, gd = gap
    best, best_cover, inner, inner_dur = "waiting for a request", 0.0, None, 0.0
    for name, s, d, _ in host:
        cover = min(gs + gd, s + d) - max(gs, s)
        if cover > best_cover:
            best, best_cover = name, cover
        if cover > gd / 2.0 and (inner is None or d < inner_dur):
            inner, inner_dur = name, d
    return best if inner is None else inner


def loop_steps(ops, start: float, dur: float) -> int:
    """How many steps a program's loop ran in one execution: inside the
    execution's interval every operation of the loop body appears once per
    step, so the commonest count over operation names is the step count."""
    counts = {}
    for name, s, _, _ in ops:
        if start <= s < start + dur:
            counts[name] = counts.get(name, 0) + 1
    if not counts:
        return 0
    tally = {}
    for c in counts.values():
        tally[c] = tally.get(c, 0) + 1
    return max(tally.items(), key=lambda kv: (kv[1], kv[0]))[0]


def chunk_width(ops, start: float, dur: float, hidden: int, widest: int) -> int:
    """How many prompt tokens one execution of a prefill-chunk program
    took in: the program's width is not in its name, but its activations
    are ``[1, width, hidden]`` (or ``[width, hidden]``), so the commonest
    leading extent (at most ``widest``, the server's full chunk) among the
    result shapes that end in the model's hidden size is the width. 0 when
    no such shape is found."""
    tally = {}
    for name, s, _, _ in ops:
        if start <= s < start + dur:
            m = re.search(r"= \(?\w+\[([\d,]+)\]", name)
            if not m:
                continue
            dims = [int(x) for x in m.group(1).split(",")]
            if (len(dims) in (2, 3) and dims[-1] == hidden and dims[-2] <= widest
                    and (len(dims) == 2 or dims[0] == 1)):
                tally[dims[-2]] = tally.get(dims[-2], 0) + 1
    return max(tally.items(), key=lambda kv: (kv[1], kv[0]))[0] if tally else 0


def _program_summary(v) -> dict:
    runs = v["runs"] if isinstance(v, dict) else v
    out = {"n": len(runs), "seconds": sum(d for _, d in runs) / 1e9}
    if isinstance(v, dict):
        out.update({k: x for k, x in v.items() if k != "runs"})
    return out


def reduce_trace(planes: dict, chips: int = 1, traced_s: float = 0.0, hidden: int = 0,
                 widest: int = 0) -> dict:
    """Busy seconds (averaged over the chips used), the traced stretch,
    time by operation and by program, and the longest idle gaps.

    The stretch is what the host's clock read between the profiler's start
    and its stop (``traced_s``), or the span from the first device
    operation to the last where that is longer: a device that idles at the
    stretch's edges has been idle, and the span alone would not count it.
    ``hidden`` is the model's hidden size and ``widest`` the server's full
    prefill chunk, by which a prefill-chunk execution's width is read off
    its operations.

    A stretch in which nothing ran on the device (no device plane, or none
    with an event) is a reading too: busy 0 s of ``traced_s``, no program,
    one idle gap, the whole stretch. The readers that need a program then
    find none and the run still ends with its result line."""
    devs = device_planes(planes)[:chips]
    spans = [
        (s, s + d)
        for p in devs for evs in planes[p].values() for _, s, d, _ in evs
    ]
    if not spans:
        return {
            "busy_s": 0.0, "window_s": traced_s, "span_s": 0.0, "device_ops": [], "programs": {},
            "idle_gaps": [["no device operation in the traced stretch", traced_s]],
        }
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    busy, op_time, programs = 0.0, {}, {}
    first_ops = []
    for p in devs:
        lines = planes[p]
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy += union_seconds([(s, d) for _, s, d, _ in ops])
        if not first_ops:
            first_ops = ops
        for name, _, d, _ in lines.get(OPS_LINE, []):
            label = op_label(name)
            if label.split(" ")[1:2] in (["while"], ["conditional"], ["call"]):
                continue  # an enclosing operation: its body's operations are listed themselves
            op_time[label] = op_time.get(label, 0.0) + d / 1e9
        for name, s, d, _ in lines.get(MODULES_LINE, []):
            programs.setdefault(module_base(name), []).append((s, d))
    # An execution's operations are those that start inside it: found by
    # bisection in the line sorted once, not by a pass over the whole line
    # for every execution (80 executions x a million operations).
    dev_ops = sorted(planes[devs[0]].get(OPS_LINE, []), key=lambda ev: ev[1])
    starts = [ev[1] for ev in dev_ops]

    def inside(s, d):
        return dev_ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts, s + d)]

    for name, prog in programs.items():
        if "decode_steps" in name:
            prog_steps = [d / 1e6 / k for s, d in prog if (k := loop_steps(inside(s, d), s, d))]
            programs[name] = {"runs": prog, "step_ms": prog_steps}
        elif "prefill_chunk" in name and hidden:
            widths = [chunk_width(inside(s, d), s, d, hidden, widest) for s, d in prog]
            programs[name] = {"runs": prog, "tokens": sum(widths), "widths_unread": widths.count(0)}
    host = host_events(planes)
    span_s = (hi - lo) / 1e9
    window_s = max(span_s, traced_s)
    # Ten are reported, so the ten longest are looked up (gaps() sorts).
    idle = [[attribute_gap(g, host), g[1] / 1e9] for g in gaps([(s, d) for _, s, d, _ in first_ops], lo, hi)[:10]]
    if window_s > span_s:
        # The trace's own clock is not the host's, so the idle time outside
        # the span cannot be split between the two edges.
        idle.append(["before the first and after the last device operation of the stretch", window_s - span_s])
    return {
        "busy_s": busy / len(devs),
        "window_s": window_s,
        "span_s": span_s,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(idle, key=lambda g: -g[1])[:10],
        "programs": {k: _program_summary(v) for k, v in programs.items()},
    }
