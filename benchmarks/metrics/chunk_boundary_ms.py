"""Scheduler: what one decode chunk's boundary costs the device. The
seconds of the passes that ran a decode chunk in which nothing the
scheduler's thread had enqueued was left on the device (the fetch of the
chunk's tokens, the emit, the next admission scan, the enqueue of the
next program: ``tpufw_serve_pass_starved_seconds_total`` of the
``decode`` and ``decode_behind_prefill`` passes), over the decode chunks
run (``tpufw_serve_ticks_total``), between the two scrapes: the whole
window, traced run or not. A lower bound of the device's gap at a
boundary: the launch after the enqueue is the device's, not booked. None
where no chunk ran, or where the program has no ledger of passes."""

from benchmarks.metrics import _passes, _prom


def read(obs: dict):
    starved = _passes.decodes(obs, _passes.STARVED)
    chunks = _prom.delta(obs, "tpufw_serve_ticks_total")
    if starved is None or not chunks:
        return None
    return 1e3 * sum(starved) / chunks
