"""Kernels: the least time the chip could take for one decode step (the
bytes it has to move — ``costs.decode_step_bytes`` at the cache tokens of
each row decoding in the traced stretch (``_steps.sample_moment``), so a
family's per-row state counts where it has one — over the chip's published
bandwidth) as a share of the decode step's measured device time.
Memory-bound: a decode step of tens of rows is far below the compute roof."""

from benchmarks import costs, harness
from benchmarks.metrics import _steps


def read(obs: dict):
    if obs["trace"] is None:
        return None
    step_ms = _steps.decode_step_ms(obs["trace"])
    if not step_ms:
        return None
    per_row = _steps.live_row_tokens(obs)
    if not per_row:
        return None  # steps in the trace and no row in the records: nothing to count bytes for
    need = costs.decode_step_bytes(obs["family"], obs["config"], len(per_row), per_row)
    floor_ms = need / harness.peaks(obs["device"]["kind"])["hbm_bytes_per_s"] * 1e3
    return 100.0 * floor_ms / step_ms
