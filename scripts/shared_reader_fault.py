"""A fault in the layers that read pages another layer wrote (a
cross-decoder over one layer's keys and values: tpufw.ops.kv_store,
READERS THAT ARE NOT THE WRITER), put through the benchmark's own harness,
which has to call the run not ``correct``:

    python scripts/shared_reader_fault.py --fault one_short -- \\
        --workload phi4flash-reason-longctx --seed <n> --seconds 45 --trace 0 [--rehearse-cpu]

Everything after ``--`` is ``benchmarks/run.py``'s own command line, and
the launcher, the phases, the load, the reference check and the limits
are its own: this script only sends each phase through itself, so that
the PROGRAM is altered before the phase imports it (as
``scripts/solar_state_fault.py`` does for the recurrent state).

- ``one_short``: every reader that is not the writer attends the arena
  one token short: the keys before the query's own position, its own key
  missing (the writer's own read is sound). What a reader that ran before
  the writer's append had landed, or a length off by one in the kernel's
  arguments, would look like to the served tokens. Both reads are
  altered alike: the ladders' (the queries' slots handed to ``attend``)
  and the kernel's in place (the lengths it walks the pages to).
"""

from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAULTS = ("one_short",)


@dataclasses.dataclass(frozen=True)
class _Short:
    """``attend`` whose paged form walks each row's pages one key short."""

    inner: object

    def __call__(self, *args):
        return self.inner(*args)

    @property
    def paged(self):
        sound = self.inner.paged
        if sound is None:
            return None
        return lambda arenas, kv_seg, table, lens, rows, **kw: sound(
            arenas, kv_seg, table, lens - (lens > 0), rows, **kw)


def break_program(fault: str) -> None:
    from tpufw.ops import kv_store

    sound = kv_store.append

    def append(module, cfg, new, segment_ids):
        read, seg, q_slots = sound(module, cfg, new, segment_ids)
        calls = []

        def short_read(attend, per_row=()):
            calls.append(attend)
            if len(calls) == 1:  # the writer's own read
                return read(attend, per_row)
            q, q_seg, slots = per_row
            return read(_Short(attend), (q, q_seg, slots - 1))

        return short_read, seg, q_slots

    kv_store.append = append


if __name__ == "__main__":
    from solar_state_fault import through_harness  # the launcher's own phases, sent through this file

    sys.exit(through_harness(__file__, __doc__, FAULTS, break_program))
