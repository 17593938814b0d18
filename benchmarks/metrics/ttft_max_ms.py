"""Service: the longest wait for a first token among the requests due in
the window (the end-to-end metric beside it is their median)."""


def read(obs: dict):
    return obs["window"].get("ttft_max_ms")
