"""Device: peak bytes in use over the chip's memory limit, as
``memory_stats()`` reports them after the window."""


def read(obs: dict):
    dev = obs["device"]
    if obs["rehearse"] or not dev.get("memory_limit_bytes"):
        return None
    return 100.0 * dev["memory_peak_bytes"] / dev["memory_limit_bytes"]
