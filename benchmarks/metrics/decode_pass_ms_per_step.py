"""Scheduler: wall milliseconds of a scheduler pass per decode step it
ran, over the passes that ran a decode chunk (``decode`` and
``decode_behind_prefill``: ``tpufw_serve_pass_seconds_total`` /
``tpufw_serve_pass_steps_total``) between the two scrapes. What a
decoding row pays a token as the scheduler sees it: the step's device
time, the chunk's boundary and the prefill programs the chunk ran behind,
all over k. It stands beside ``tpot_p50_ms`` (the client's median) and
``decode_step_dev_ms`` (the device's step). None where no decode step
ran, or where the program has no ledger of passes."""

from benchmarks.metrics import _passes


def read(obs: dict):
    seconds = _passes.decodes(obs, _passes.SECONDS)
    steps = _passes.decodes(obs, _passes.STEPS)
    if seconds is None or steps is None or sum(steps) <= 0:
        return None
    return 1e3 * sum(seconds) / sum(steps)
