"""A fault in a family's per-slot recurrent state (Solar-Open2's linear
attention, Falcon-H1's state-space mixer, Olmo-Hybrid's Gated DeltaNet,
Phi-4-mini-flash's Mamba-1 scan: the family is the workload's),
put through the benchmark's own harness, which has to call the run not
``correct``:

    python scripts/solar_state_fault.py --fault zero_carry|bf16_state -- \\
        --workload solar2-longdoc-answers|falconh1-instruct-burst|olmoh-reason-pool|phi4flash-reason-longctx --seed <n> --seconds 45 --trace 0 [--rehearse-cpu]

Everything after ``--`` is ``benchmarks/run.py``'s own command line, and
the launcher, the phases, the load, the reference check and the limits
are its own: this script only sends each phase through itself, so that
the PROGRAM is altered before the phase imports it.

- ``zero_carry``: every call of the chunkwise rule starts from a zero
  state, so what a prompt's earlier chunks wrote is lost at each
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


#: family -> (the model's module, the chunkwise rule as that module binds
#: it, which of its arguments is the carried state, the state's type).
STATEFUL = {
    "solar_open2": ("tpufw.models.solar_open2", "kda_chunk", 5, "KDA_STATE_DTYPE"),
    "falcon_h1": ("tpufw.models.falcon_h1", "ssd_chunk", 6, "SSM_STATE_DTYPE"),
    "olmo_hybrid": ("tpufw.models.olmo_hybrid", "kda_chunk", 5, "GDN_STATE_DTYPE"),
    "phi4flash": ("tpufw.models.phi4flash", "selective_chunk", 6, "MAMBA_STATE_DTYPE"),
}


def family_of(argv) -> str:
    """The family of the configuration the ``--workload`` of ``argv`` runs."""
    from benchmarks import harness

    bench = harness.load_benchmark()
    cell = harness.cell(bench, argv[argv.index("--workload") + 1])
    return harness.load_json(harness.config_entry(bench, cell["config"])["file"])["family"]


def break_program(fault: str, family: str) -> None:
    import importlib

    import jax.numpy as jnp

    module, rule, state_at, state_dtype = STATEFUL[family]
    model = importlib.import_module(module)
    if fault == "bf16_state":
        setattr(model, state_dtype, jnp.bfloat16)
        return
    sound = getattr(model, rule)

    def forgets(*args, **kwargs):
        args = list(args)
        args[state_at] = jnp.zeros_like(args[state_at])
        return sound(*args, **kwargs)

    setattr(model, rule, forgets)


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
    sys.exit(through_harness(
        __file__, __doc__, FAULTS, lambda fault: break_program(fault, family_of(sys.argv))))
