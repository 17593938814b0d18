"""Find a serving cell's knee: ONE server as the cell's serve phase builds
it (weights from the seed, every prefill width and decode length warmed),
then window after window of the cell's own length at each offered rate,
several schedules a rate, and the knee by the mixes' rule.

    python scripts/bench_sweep.py --workload <cell> --rates 0.1,0.15,0.2,0.25 --shapes 0,1,2 [--seed n]

A rung is one rate; its windows differ in ``shape_seed`` (the mix's own
value orders the stratified draws of arrivals and lengths; another value
gives another schedule of the same distribution) and in the seed of the
token ids, so a rung's share is over several dozen requests and not one
window's handful. Each window's mix is a copy under ``.bench-scratch/``
handed to this script's own load generator (``--as-client``): no tracked
file is touched. Between windows the server runs empty.

The rule (``knee.rule`` of the mix files): TPOT limit = 2 x the median
TPOT at the lowest rate; TTFT limit = 5 x the median TTFT there per
prompt-length bucket (a bucket no prompt fell into takes the next one's);
the knee is the highest rate at which >= 90% of the requests due in the
rung's windows meet both and the windows end with no larger a backlog
than they began. The backlog is the server's QUEUE
(``tpufw_serve_queue_depth``: submitted and not yet admitted to a slot) at
the scrapes that open and close each window, summed over the rung's
windows; rows in service (rate x service time, which moves by one or two
with where the last arrivals fall) are printed beside it and decide
nothing. Needs the chip: a rate is a device number.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness, procs, stats, traffic  # noqa: E402
from benchmarks.metrics import _prom  # noqa: E402

BUCKETS = (512, 2048, 8192, 16384)
QUEUE = "tpufw_serve_queue_depth"
#: Host traces of the row model: a pool's one, none between two scrapes.
ROW_TRACES = "tpufw_serve_row_shape_traces_total"
#: Decode steps of a pool with routed experts, and those of them that ran
#: the experts over the live rows' assignments alone (tpufw.ops.moe_live).
EXPERT_STEPS = "tpufw_serve_expert_steps_total"
EXPERT_LIVE = "tpufw_serve_expert_live_steps_total"


def limits_from(records_t0, seconds):
    """The rule's limits from the lowest rung: ``records_t0`` is a list of
    (records, t0), one per window."""
    due = [r for recs, t0 in records_t0 for r in recs if t0 <= r["due"] < t0 + seconds and r["chunks"]]
    tpots = [tp for r in due if (tp := stats.tpot_s(r["chunks"], min_tokens=16)) is not None]
    ttft, prev = {}, None
    for edge in reversed(BUCKETS):
        lo = max([b for b in BUCKETS if b < edge], default=0)
        own = [(r["chunks"][0][0] - r["due"]) * 1e3 for r in due if lo < r["n_prompt"] <= edge]
        prev = 5.0 * statistics.median(own) if own else prev
        ttft[str(edge)] = prev
    for edge in BUCKETS:  # an empty top bucket takes the one below
        if ttft[str(edge)] is None:
            ttft[str(edge)] = next(v for v in ttft.values() if v is not None)
    return {"tpot_ms": 2e3 * statistics.median(tpots), "ttft_ms": ttft}


def as_client(a) -> int:
    """The load generator of one window: the schedule of the mix FILE it
    is given, announced and driven as ``benchmarks/client.py`` does, and
    every request waited for, so the next window finds the server empty."""
    from benchmarks import client

    procs.die_with_parent()
    with open(a.mix) as f:
        mix = json.load(f)
    reqs = traffic.schedule(mix, a.seed, a.seconds, a.vocab)
    run = asyncio.run(client.drive(
        "127.0.0.1", a.port, reqs, a.seconds, a.drain,
        lambda t0: print(json.dumps({"t0": t0}), flush=True), until="done"))
    with open(a.out, "w") as f:
        json.dump(run, f)
    return 0


def all_widths(mix: dict, posture: dict) -> list:
    """(prompt length, max_new) pairs that build every program any schedule
    of this mix can reach under the server's ``posture`` (the mix's
    ``server_env``): each page-granular tail width a prompt length
    of the mix's quantum leaves, the full chunk, the decode ladder."""
    page = int(posture["TPUFW_SERVE_PAGE"])
    chunk = int(posture["TPUFW_SERVE_PREFILL_CHUNK"]) * page
    p = mix["prompt"]
    q = int(p.get("quantum", 1))
    lengths = range(max(q, -(-int(p["base"]) // q) * q), int(p["cap"]) + 1, q)
    widths = sorted({chunk} | {-(-(n - ((n - 1) // chunk) * chunk) // page) * page for n in lengths})
    ks, k = [], int(posture["TPUFW_SERVE_CHUNK"])
    while k >= 1:
        ks.append(k)
        k //= 2
    n = max(len(widths), len(ks))
    return [(widths[i % len(widths)], ks[i % len(ks)] + 1) for i in range(n)]


def sweep_windows(a, rates, shapes, out):
    """What takes the place of the serve phase's one window."""
    from benchmarks.runners import serve as runner

    began = time.time()

    def windows(args, bench, cell, config, mix, keys, check, reqs, host, port,
                compiles, setup_s, out_dir, client, jax):
        seconds = float(args.seconds)
        scrape = lambda: runner._parse_prom(asyncio.run(client.http_get(host, port, "/metrics")))
        rows, n = [], 0
        for shape in shapes:
            for rate in rates:
                if a.budget_s and time.time() - began > a.budget_s:
                    runner.say(f"sweep: {a.budget_s:.0f} s spent, no further window")
                    break
                n += 1
                own = {**mix, "shape_seed": shape, "arrivals": {**mix["arrivals"], "rate_rps": rate}}
                tag = f"rate_{rate}.shape_{shape}"
                mix_path, rec_path = os.path.join(out_dir, tag + ".mix.json"), os.path.join(out, tag + ".records.json")
                with open(mix_path, "w") as f:
                    json.dump(own, f)
                # The server empty before the ramp: nothing left of the last window.
                deadline = time.time() + 90
                while time.time() < deadline:
                    prom = scrape()
                    if not prom.get("tpufw_serve_slots_occupied") and not prom.get(QUEUE):
                        break
                    time.sleep(0.5)
                gen = subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__), "--as-client", "--mix", mix_path, "--port", str(port),
                     "--seed", str(args.seed + n), "--seconds", str(seconds), "--drain", str(float(mix["drain_s"])),
                     "--vocab", str(keys["vocab_size"]), "--out", rec_path],
                    cwd=harness.ROOT, env=procs.child_env(), stdout=subprocess.PIPE, text=True)
                try:
                    t0 = json.loads(gen.stdout.readline())["t0"]
                    time.sleep(max(0.0, t0 - time.time()))
                    prom0, c0 = scrape(), compiles.n
                    time.sleep(max(0.0, t0 + seconds - time.time()))
                    prom1, c1 = scrape(), compiles.n
                    if gen.wait(timeout=float(mix["drain_s"]) + 120) != 0:
                        raise RuntimeError("the load generator failed")
                finally:
                    if gen.poll() is None:
                        gen.kill()
                        gen.wait()
                with open(rec_path) as f:
                    run = json.load(f)
                rows.append({"rate_rps": rate, "shape_seed": shape, "seed": args.seed + n, "t0": t0,
                             "cutoff": run["cutoff"], "records": run["records"],
                             "queue_start": prom0.get(QUEUE, 0.0), "queue_end": prom1.get(QUEUE, 0.0),
                             "compiled_in_window": c1 - c0,
                             "row_shape_traces": [prom0.get(ROW_TRACES), prom1.get(ROW_TRACES)]})
                ws = stats.window_stats(run["records"], t0, seconds, run["cutoff"], {}, cell["chips"])
                # None where the server has no such counter (an older tree).
                grew = lambda name: _prom.delta({"prom0": prom0, "prom1": prom1}, name)
                runner.say(f"sweep: window {n} rate {rate} shape {shape}: attempted {ws['attempted']} failed {ws['failed']} "
                           f"tokens/s {ws['tokens_per_s_per_chip']:.2f} tpot_p50 {ws.get('tpot_p50_ms', 0):.2f} "
                           f"ttft_p50 {ws.get('ttft_p50_ms', 0):.0f} queue {rows[-1]['queue_start']:.0f} -> "
                           f"{rows[-1]['queue_end']:.0f} in service {ws['backlog_start']} -> {ws['backlog_end']} "
                           f"programs built {c1 - c0} row shape traces {prom0.get(ROW_TRACES)} -> {prom1.get(ROW_TRACES)} "
                           f"expert steps on the live rows alone {grew(EXPERT_LIVE)} of {grew(EXPERT_STEPS)}; "
                           f"{time.time() - began:.0f} s so far")
        summarise(a, cell, seconds, setup_s, rows, out)

    runner._window = windows
    runner.warmup_requests = lambda reqs, env: all_widths(a.the_mix, env)


def summarise(a, cell, seconds, setup_s, rows, out) -> None:
    if not rows:
        return
    rates = sorted({r["rate_rps"] for r in rows})
    lowest = [(r["records"], r["t0"]) for r in rows if r["rate_rps"] == rates[0]]
    limits = limits_from(lowest, seconds)
    table = []
    for rate in rates:
        own = [r for r in rows if r["rate_rps"] == rate]
        per = []
        for r in own:
            ws = stats.window_stats(r["records"], r["t0"], seconds, r["cutoff"], limits, cell["chips"])
            ws.pop("late_ms")
            per.append({"shape_seed": r["shape_seed"], "seed": r["seed"], "queue_start": r["queue_start"],
                        "queue_end": r["queue_end"], "compiled_in_window": r["compiled_in_window"],
                        "row_shape_traces": r["row_shape_traces"], **ws})
        attempted = sum(w["attempted"] for w in per)
        good = sum(round(w.get("slo_good_share", 0.0) * w["attempted"] / 100.0) for w in per)
        q0, q1 = sum(w["queue_start"] for w in per), sum(w["queue_end"] for w in per)
        share = 100.0 * good / attempted
        table.append({
            "rate_rps": rate, "windows": len(per), "attempted": attempted, "met_both": good, "good_share": share,
            "failed": sum(w["failed"] for w in per), "queue_start": q0, "queue_end": q1,
            "in_service_start": sum(w["backlog_start"] for w in per), "in_service_end": sum(w["backlog_end"] for w in per),
            "sustained": share >= 90.0 and q1 <= q0,
            "tokens_per_s_per_chip": [w["tokens_per_s_per_chip"] for w in per],
            "tpot_p50_ms": [w.get("tpot_p50_ms") for w in per], "ttft_p50_ms": [w.get("ttft_p50_ms") for w in per],
            "per_window": per,
        })
    knee = max((row["rate_rps"] for row in table if row["sustained"]), default=None)
    summary = {"workload": a.workload, "rule_backlog": QUEUE, "setup_s": setup_s, "limits": limits,
               "knee_rps": knee, "rows": table}
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for row in table:
        row.pop("per_window")
    print("sweep summary " + json.dumps(summary), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload")
    ap.add_argument("--rates", default="")
    ap.add_argument("--shapes", default="0,1,2", help="shape seeds, a window each at every rate")
    ap.add_argument("--seed", type=int, default=2147483000)
    ap.add_argument("--budget-s", type=float, default=0.0, help="start no window after this many seconds")
    ap.add_argument("--rehearse-cpu", action="store_true", help="the control flow on the CPU at tiny widths, 4 s windows")
    ap.add_argument("--as-client", action="store_true", help="internal: one window's load generator")
    for name, kind in (("mix", str), ("port", int), ("seconds", float), ("drain", float), ("vocab", int), ("out", str)):
        ap.add_argument("--" + name, type=kind, help="internal")
    a = ap.parse_args()
    if a.as_client:
        return as_client(a)
    from benchmarks import run
    from benchmarks.runners import serve as runner

    bench = harness.load_benchmark()
    cell = harness.cell(bench, a.workload)
    config = harness.load_json(harness.config_entry(bench, cell["config"])["file"])
    a.the_mix = harness.cell_inputs(cell, config, a.rehearse_cpu)[0]
    out = os.path.join(ROOT, "chiprun_out", "sweep", a.workload)
    os.makedirs(out, exist_ok=True)
    argv = ["--workload", a.workload, "--seed", str(a.seed), "--trace", "0", "--phase", "serve",
            "--seconds", str(4 if a.rehearse_cpu else bench["run_seconds"])] + (["--rehearse-cpu"] if a.rehearse_cpu else [])
    args = run.parse(argv)
    args.t0 = time.time()
    sweep_windows(a, sorted(float(r) for r in a.rates.split(",")), [int(s) for s in a.shapes.split(",")], out)
    # The scheduler's thread never ends: leave as a phase does.
    procs.exit_after(lambda: runner.serve_phase(args, bench, cell, config))


if __name__ == "__main__":
    sys.exit(main())
