"""Kernels: the share of the cache rows' key slots that the cached calls
dispatched in the window read. The program attends, gathers and converts
the live prefix of a row, rounded up to a rung of a short ladder of key
lengths chosen inside each program; the scheduler counts the rung of
every decode step, speculative pass and prefill chunk it dispatched
(``tpufw_serve_attended_key_slots_total``) beside the whole rows
(``tpufw_serve_row_key_slots_total``), rows x slots each. 100 = every
call read all of ``max_seq_len``. A program without the counters reports
nothing."""

from benchmarks.metrics import _prom


def read(obs: dict):
    attended = _prom.delta(obs, "tpufw_serve_attended_key_slots_total")
    whole = _prom.delta(obs, "tpufw_serve_row_key_slots_total")
    if attended is None or not whole:
        return None
    return 100.0 * attended / whole
