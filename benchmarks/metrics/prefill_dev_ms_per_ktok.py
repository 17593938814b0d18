"""Model step: device milliseconds the prefill-chunk program took per
thousand prompt tokens it took in, over the traced stretch (both from
the same executions, as ``prefill_mfu_share`` reads them)."""


def read(obs: dict):
    tr = obs["trace"]
    if tr is None:
        return None
    progs = [p for name, p in tr["programs"].items() if "prefill_chunk" in name]
    secs = sum(p["seconds"] for p in progs)
    tokens = sum(p.get("tokens", 0) for p in progs)
    if secs <= 0 or tokens <= 0 or any(p.get("widths_unread") for p in progs):
        return None
    return secs / tokens * 1e6
