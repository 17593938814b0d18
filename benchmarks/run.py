"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration's runner under
``benchmarks/runners/`` and hands over. The last line of standard output
is the result object; a run that finds no TPU exits non-zero and prints
none. ``--rehearse-cpu`` runs the same control flow at tiny widths on the
CPU, says so, and prints no device metric.

Before it starts any process the launcher checks, without importing jax,
that every file the cell is made of is there (``harness.missing_parts``):
a missing one is a line naming it and exit code 2. No process a run starts
outlives it, however it ends (``benchmarks/procs.py``).

Two options exist for the tests that hold the harness to its word, and the
driver passes neither: ``--control int8_weights`` runs the cell with the
program's own weight-only int8 path switched on, which has to come out not
correct; ``--break token`` alters every token where the pools sample it,
and ``--break raise`` raises in the serve phase once its window has closed.
"""

from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness, procs  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--phase", default="", help="internal: one phase of a run")
    ap.add_argument("--t0", type=float, default=0.0, help="internal: the launcher's start")
    ap.add_argument("--control", default="", choices=("", "int8_weights"),
                    help="tests: run the control of `correct` (see PERF.md)")
    ap.add_argument("--break", dest="broken", default="", choices=("", "token", "raise"),
                    help="tests: break the timed path")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.phase:
        procs.die_with_parent()
    args.t0 = args.t0 or T0
    bench = harness.load_benchmark()
    cell = harness.cell(bench, args.workload)
    entry = harness.config_entry(bench, cell["config"])
    config = harness.load_json(entry["file"])
    if not args.phase:
        missing = harness.missing_parts(bench, cell, config)
        for line in missing:
            print(f"bench: cell {cell['name']} cannot run: {line}", file=sys.stderr)
        if missing:
            return 2
    runner = importlib.import_module(f"benchmarks.runners.{config['runner']}")
    return runner.main(args, bench, cell, config)


if __name__ == "__main__":
    sys.exit(main())
