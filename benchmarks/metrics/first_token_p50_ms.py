"""Service: the median wait for a first token among the requests due in
the window, in a cell where that median is one request's number and is
not held end to end (``ttft_p50_ms`` is the same statistic in the cells
that hold it; PERF.md section 2 says which and why)."""


def read(obs: dict):
    return obs["window"].get("ttft_p50_ms")
