"""Scheduler: the share of decode chunks that were enqueued before their
predecessor was read (``tpufw_serve_chunks_chained_total``: the slot
scheduler's chained order, taken at a chunk's boundary where nothing is
queued, nobody prefills and a row has budget left, so the fetch, the
emit and the next admission scan run beside the device) over the decode
chunks read (``tpufw_serve_ticks_total``), between the two scrapes: the
whole window, traced run or not. The boundaries it leaves out are the
plain order's: an arrival or a prompt's chunks came between two chunks,
or the chunk was a request's last. None where no chunk ran, or where the
program has no such counter (every commit before it was added)."""

from benchmarks.metrics import _prom


def read(obs: dict):
    chained = _prom.delta(obs, "tpufw_serve_chunks_chained_total")
    chunks = _prom.delta(obs, "tpufw_serve_ticks_total")
    if chained is None or not chunks:
        return None
    return 100.0 * chained / chunks
