"""A fault in the window layers, put through the benchmark's own harness,
which has to call the run not ``correct``:

    python scripts/laguna_window_fault.py --fault whole_row -- \\
        --workload laguna-repo-context --seed <n> --seconds 45 --trace 0 [--rehearse-cpu]

Everything after ``--`` is ``benchmarks/run.py``'s own command line, and
the launcher, the phases, the load, the reference check and the limits
are its own: this script only sends each phase through itself, so that
the PROGRAM is altered before the phase imports it (as
``scripts/solar_state_fault.py`` does for the linear-attention state).

- ``whole_row``: every sliding-window layer attends its whole row: no
  window, so no ring either; its keys and values go to the page arena as
  a global layer's do. What a lost window mask would look like to the
  served tokens: every prompt of the cell is at least four windows long.
  At the published widths, 8 slots of 16,384 do not fit the chip this way
  (my chip run, PR 32: 15.80 GB of 15.75, the arena of all eight layers
  beside the weights): there the fault to run is the next one.
- ``wide_ring``: every sliding-window layer keeps and attends a ring four
  windows wide (2,048 keys for 512): a ring that kept more than the
  window and masked nothing of it. It fits wherever the sound program
  does (0.4 GB of rings for 0.1).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAULTS = ("whole_row", "wide_ring")


def break_program(fault: str) -> None:
    from tpufw.models import laguna

    sound = laguna.Attention

    def sees_too_much(cfg, *, window, **kw):
        if fault == "whole_row" or window is None:
            return sound(cfg, window=None, **kw)
        return sound(cfg, window=4 * window, **kw)

    laguna.Attention = sees_too_much


if __name__ == "__main__":
    from solar_state_fault import through_harness  # the launcher's own phases, sent through this file

    sys.exit(through_harness(__file__, __doc__, FAULTS, break_program))
