"""Device: share of the traced stretch in which no operation ran on the
chip (1 - union of device-operation intervals over the stretch)."""


def read(obs: dict):
    tr = obs["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
