"""Scheduler: median wait from submit to the start of the admission pass
that seated the request, from the server's histogram
``tpufw_serve_queue_wait_seconds`` over the window. With
``prefill_span_p50_ms`` it splits the client's time to first token."""

from benchmarks.metrics import _prom


def read(obs: dict):
    q = _prom.histogram_quantile(obs, "tpufw_serve_queue_wait_seconds", 0.5)
    return None if q is None else q * 1e3
