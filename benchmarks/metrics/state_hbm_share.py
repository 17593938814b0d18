"""Device: the per-slot state the pool holds beside its pages (the
linear-attention layers' recurrent state and convolution tails, all
slots: ``tpufw_serve_state_bytes`` at the window's second scrape) over
the chip's published memory. A program without the gauge reports
nothing."""

from benchmarks import harness


def read(obs: dict):
    held = obs["prom1"].get("tpufw_serve_state_bytes")
    if held is None or obs["rehearse"]:
        return None
    return 100.0 * held / harness.peaks(obs["device"]["kind"])["hbm_bytes"]
