"""Scheduler: share of the slot-steps the decode pool ran in the window
that delivered no token (empty slots, rows done inside a chunk). Slot-steps
run = wasted + tokens generated by decode steps; the first token of each
request comes out of prefill, so the joins are taken off."""

from benchmarks.metrics import _prom


def read(obs: dict):
    wasted = _prom.delta(obs, "tpufw_serve_wasted_slot_steps_total")
    tokens = _prom.delta(obs, "tpufw_serve_tokens_generated_total")
    joins = _prom.delta(obs, "tpufw_serve_join_latency_seconds_count") or 0.0
    if wasted is None or tokens is None:
        return None
    ran = wasted + tokens - joins
    return 100.0 * wasted / ran if ran > 0 else None
