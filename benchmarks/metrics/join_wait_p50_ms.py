"""Scheduler: median wait from submit to the first slot insert, from the
server's histogram ``tpufw_serve_join_latency_seconds`` over the window."""

from benchmarks.metrics import _prom


def read(obs: dict):
    q = _prom.histogram_quantile(obs, "tpufw_serve_join_latency_seconds", 0.5)
    return None if q is None else q * 1e3
