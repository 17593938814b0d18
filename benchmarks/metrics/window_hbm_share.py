"""Device: what the pool holds for the layers that attend a window, the
rings of their last keys and values with each ring slot's position and
segment id, all slots (``tpufw_serve_window_bytes`` at the window's second
scrape), over the chip's published memory. It is slots x window x layers
whatever the context; the same rows on the page arena would hold slots x
``max_seq_len``. A program without the gauge reports nothing."""

from benchmarks import harness


def read(obs: dict):
    held = obs["prom1"].get("tpufw_serve_window_bytes")
    if held is None or obs["rehearse"]:
        return None
    return 100.0 * held / harness.peaks(obs["device"]["kind"])["hbm_bytes"]
