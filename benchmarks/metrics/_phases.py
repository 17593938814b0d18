"""The scheduler thread's seconds by phase, between the window's two
scrapes: deltas of ``tpufw_serve_phase_seconds_total{phase="<span>"}``,
the self time of each span of the slot scheduler's pass. A program
without the counter (every commit before it was added) gives None."""

from __future__ import annotations

import re

SERIES = re.compile(r'tpufw_serve_phase_seconds_total\{phase="([^"]+)"\}')


def deltas(obs: dict):
    """{phase: seconds in the window}, or None where the program exposes
    no such series."""
    out = {}
    for key, after in obs["prom1"].items():
        m = SERIES.fullmatch(key)
        if m:
            out[m.group(1)] = after - obs["prom0"].get(key, 0.0)
    return out or None
