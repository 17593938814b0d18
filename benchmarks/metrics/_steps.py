"""What the decode program did in the traced stretch, shared by the
readers that divide by it."""

from __future__ import annotations

import statistics


def decode_program(trace: dict):
    for name, prog in trace["programs"].items():
        if "decode_steps" in name:
            return prog
    return None


def decode_step_ms(trace: dict):
    """Median device milliseconds of one decode step: each execution of the
    decode program over the steps its loop ran."""
    prog = decode_program(trace)
    if not prog or not prog.get("step_ms"):
        return None
    return statistics.median(prog["step_ms"])


def live_row_tokens(obs: dict) -> list:
    """Cache tokens (prompt + generated so far) of each row decoding at
    the window's midpoint, from the client's records."""
    mid = obs["t0"] + obs["seconds"] / 2.0
    out = []
    for r in obs["records"]:
        if not r["chunks"] or r["chunks"][0][0] > mid:
            continue
        if r["done"] is not None and r["done"] <= mid:
            continue
        out.append(r["n_prompt"] + sum(n for t, n in r["chunks"] if t <= mid))
    return out


def live_rows_and_tokens(obs: dict):
    """Rows decoding and cache tokens live at the window's midpoint."""
    per_row = live_row_tokens(obs)
    return len(per_row), sum(per_row)
