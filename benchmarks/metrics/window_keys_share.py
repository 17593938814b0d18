"""Kernels: of the key slots that whole cache rows would show the layers
that attend a window, the share those layers read. The scheduler counts,
for every decode step and prefill chunk it dispatched, rows x ring slots
read x window layers (``tpufw_serve_window_key_slots_total``: the ring
after a decode step's own write, the ring beside a chunk's own tokens)
beside rows x ``max_seq_len`` x window layers
(``tpufw_serve_window_row_key_slots_total``). 100 = a window layer read
whole rows, as a layer on the page arena does at its top rung; the global
layers' own share is ``attended_keys_share``. A program without the
counters, or a model without window layers, reports nothing."""

from benchmarks.metrics import _prom


def read(obs: dict):
    read_slots = _prom.delta(obs, "tpufw_serve_window_key_slots_total")
    whole = _prom.delta(obs, "tpufw_serve_window_row_key_slots_total")
    if read_slots is None or not whole:
        return None
    return 100.0 * read_slots / whole
