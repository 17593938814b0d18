"""What the decode program did in the traced stretch, and which rows were
decoding there, shared by the readers that divide by it."""

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


def _decoding_at(records, at: float) -> list:
    """Cache tokens (prompt + generated so far) of each row decoding at
    ``at``: it has its first chunk and is not done."""
    out = []
    for r in records:
        if not r["chunks"] or r["chunks"][0][0] > at:
            continue
        if r["done"] is not None and r["done"] <= at:
            continue
        out.append(r["n_prompt"] + sum(n for t, n in r["chunks"] if t <= at))
    return out


def sample_moment(obs: dict):
    """(the moment at which the live rows are counted, on the client's
    clock; what it is, in words). With a trace, the middle of the traced
    stretch: what is divided by the trace's steps is counted where the
    trace was taken. The faster the server, the likelier that no row
    decodes at that one moment while the stretch still holds decode steps;
    a row decodes between any two of its chunks, so the moment is then
    halfway between the two chunks nearest the middle, of those whose
    later one was received inside the stretch. Without a trace, mid-window."""
    lo, length = obs.get("traced_from"), obs.get("traced_s")
    if lo is None:
        return obs["t0"] + obs["seconds"] / 2.0, "mid-window"
    mid = lo + length / 2.0
    between = [] if _decoding_at(obs["records"], mid) else [
        (a + b) / 2.0
        for r in obs["records"] for (a, _), (b, _) in zip(r["chunks"], r["chunks"][1:])
        if lo <= b <= lo + length
    ]
    if not between:
        return mid, "the middle of the traced stretch"
    return min(between, key=lambda t: abs(t - mid)), "the decode chunk nearest the middle of the traced stretch"


def prefilling_prompts(obs: dict) -> list:
    """Lengths of the prompts that were prefilling at some moment of the
    traced stretch: due by its end, no first token by its start. Without a
    trace's moments, or where the records show none (a chunk in the trace
    whose request the client saw outside it), the prompts due in the window."""
    t0, lo = obs["t0"], obs.get("traced_from")
    if lo is not None:
        hi = lo + obs["traced_s"]
        inside = [r["n_prompt"] for r in obs["records"]
                  if r["due"] < hi and (not r["chunks"] or r["chunks"][0][0] > lo)]
        if inside:
            return inside
    return [r["n_prompt"] for r in obs["records"] if t0 <= r["due"] < t0 + obs["seconds"]]


def live_row_tokens(obs: dict) -> list:
    """Cache tokens of each row decoding at ``sample_moment``, from the
    client's records: the one count of live rows that every reader pairing
    the records with the trace uses."""
    return _decoding_at(obs["records"], sample_moment(obs)[0])


def live_rows_and_tokens(obs: dict):
    """Rows decoding and cache tokens live at ``sample_moment``."""
    per_row = live_row_tokens(obs)
    return len(per_row), sum(per_row)
