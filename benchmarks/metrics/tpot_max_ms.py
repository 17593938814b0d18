"""Service: the slowest request's time per output token in the window (the
end-to-end metric beside it is the median over the same requests)."""


def read(obs: dict):
    return obs["window"].get("tpot_max_ms")
