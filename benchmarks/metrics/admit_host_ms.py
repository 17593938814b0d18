"""Scheduler: host time one admission takes from the running decodes:
the thread's self seconds in ``serve_admit`` (queue scan, page grants,
prefix lookup) and ``serve_row_alloc`` (the row cache's allocation, which
re-traces the row model), over the requests admitted in the window (the
count of ``tpufw_serve_join_latency_seconds``). None when nothing was
admitted, or where the program has no phase counter."""

from benchmarks.metrics import _phases, _prom


def read(obs: dict):
    phases = _phases.deltas(obs)
    joins = _prom.delta(obs, "tpufw_serve_join_latency_seconds_count")
    if phases is None or not joins:
        return None
    return 1e3 * (phases.get("serve_admit", 0.0) + phases.get("serve_row_alloc", 0.0)) / joins
