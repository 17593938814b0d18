"""Scheduler: what a decode step loses to the neighbours' prefill, per
step of the window. The milliseconds a step cost in the passes whose
decode chunk ran behind a prefill program or insert of the same pass
(``decode_behind_prefill``), less what it cost in the passes that ran
nothing ahead of their chunk (``decode``), times the share of the
window's steps that ran behind prefill: the part of
``decode_pass_ms_per_step`` that pacing or fusing the prefill turns
could take away. 0.0 where no pass ran behind prefill; None where no
``decode`` pass ran (nothing to take the difference from), or where the
program has no ledger of passes."""

from benchmarks.metrics import _passes


def read(obs: dict):
    seconds = _passes.decodes(obs, _passes.SECONDS)
    steps = _passes.decodes(obs, _passes.STEPS)
    if seconds is None or steps is None or steps[0] <= 0:
        return None
    if steps[1] <= 0:
        return 0.0
    apart = seconds[1] / steps[1] - seconds[0] / steps[0]
    return 1e3 * apart * steps[1] / sum(steps)
