"""Scheduler: median time from a row's slot grant to its first token
sampled, across every scheduler pass its chunked prefill took, from the
server's histogram ``tpufw_serve_prefill_seconds`` over the window."""

from benchmarks.metrics import _prom


def read(obs: dict):
    q = _prom.histogram_quantile(obs, "tpufw_serve_prefill_seconds", 0.5)
    return None if q is None else q * 1e3
