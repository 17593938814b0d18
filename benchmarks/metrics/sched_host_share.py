"""Scheduler: share of the window in which the scheduler thread did host
work while the device had nothing new from it. The sum of the thread's
self seconds in every phase except ``serve_wait`` (nothing queued,
nothing running) and ``serve_device_wait`` (blocked on the device), from
``tpufw_serve_phase_seconds_total`` between the two scrapes, over the
window's length. Covers the whole window, traced run or not."""

from benchmarks.metrics import _phases

NOT_HOST = ("serve_wait", "serve_device_wait")


def read(obs: dict):
    phases = _phases.deltas(obs)
    if phases is None or obs["seconds"] <= 0:
        return None
    host = sum(s for name, s in phases.items() if name not in NOT_HOST)
    return 100.0 * host / obs["seconds"]
