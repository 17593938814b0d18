"""A fault in the linear-attention state, put through the benchmark's own
harness, which has to call the run not ``correct``:

    python scripts/solar_state_fault.py --fault zero_carry|bf16_state -- \\
        --workload solar2-longdoc-answers --seed <n> --seconds 45 --trace 0 [--rehearse-cpu]

Everything after ``--`` is ``benchmarks/run.py``'s own command line, and
the launcher, the phases, the load, the reference check and the limits
are its own: this script only sends each phase through itself, so that
the PROGRAM is altered before the phase imports it.

- ``zero_carry``: every call of the chunkwise delta rule starts from a
  zero state, so what a prompt's earlier chunks wrote is lost at each
  chunk boundary (the convolution tails and the softmax layer's keys and
  values are carried as ever). What a chunk-boundary bug, or a slot that
  kept another row's state, would look like to the served tokens.
- ``bf16_state``: the recurrent state kept in bfloat16 between steps,
  where the configuration states float32.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAULTS = ("zero_carry", "bf16_state")


def break_program(fault: str) -> None:
    import jax.numpy as jnp

    from tpufw.models import solar_open2

    if fault == "bf16_state":
        solar_open2.KDA_STATE_DTYPE = jnp.bfloat16
        return
    sound = solar_open2.kda_chunk

    def forgets(q, k, v, g, beta, state, valid=None):
        return sound(q, k, v, g, beta, jnp.zeros_like(state), valid)

    solar_open2.kda_chunk = forgets


def through_harness(script: str, doc: str, faults, break_program) -> int:
    """``benchmarks/run.py`` with each of its phases sent through
    ``script``, whose ``break_program(fault)`` alters the served program
    before the serve phase imports it (scripts/laguna_window_fault.py
    calls this too)."""
    ap = argparse.ArgumentParser(description=doc)
    ap.add_argument("--fault", required=True, choices=faults)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    a = ap.parse_args()
    rest = a.rest[1:] if a.rest[:1] == ["--"] else a.rest
    from benchmarks import run
    from benchmarks.runners import serve as runner

    if "--phase" in rest:
        if rest[rest.index("--phase") + 1] == "serve":
            break_program(a.fault)
        return run.main(rest)
    plain = runner._phase_cmd
    # run.py's own phase command, with the script in front of its arguments.
    runner._phase_cmd = lambda args, phase: (
        [sys.executable, os.path.abspath(script), "--fault", a.fault, "--"] + plain(args, phase)[2:]
    )
    print(f"bench: FAULT {a.fault} in the served program (scripts/{os.path.basename(script)}): this run has to be not correct",
          file=sys.stderr, flush=True)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(through_harness(__file__, __doc__, FAULTS, break_program))
