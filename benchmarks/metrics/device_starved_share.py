"""Scheduler: of the seconds the server had requests in service, the
share in which the device had nothing from the scheduler's thread: the
starved seconds of every pass over the seconds of every pass
(``tpufw_serve_pass_starved_seconds_total`` /
``tpufw_serve_pass_seconds_total``) between the two scrapes. What
``device_idle_share`` says of a stretch that holds work, over the whole
window and without a profiler, and a lower bound of it there: the host's
share of the device's idle time, where ``device_idle_share`` also holds
how empty the offered load leaves the stretch. None where no pass ran,
or where the program has no ledger of passes."""

from benchmarks.metrics import _passes


def read(obs: dict):
    starved = _passes.by_kind(obs, _passes.STARVED)
    seconds = _passes.by_kind(obs, _passes.SECONDS)
    if starved is None or seconds is None or sum(seconds.values()) <= 0:
        return None
    return 100.0 * sum(starved.values()) / sum(seconds.values())
