"""Service: share of the requests due in the window that met both of the
cell's limits, TTFT by prompt-length bucket and TPOT (the traffic file
derives them from the sweep). Per-layer and unbounded: with a dozen long
requests in a window one request is nine points."""


def read(obs: dict):
    return obs["window"].get("slo_good_share")
