"""Model step: device time of the decode program per decode step, median
over the executions in the traced stretch (device trace)."""

from benchmarks.metrics import _steps


def read(obs: dict):
    return None if obs["trace"] is None else _steps.decode_step_ms(obs["trace"])
