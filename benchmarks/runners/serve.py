"""The serving runner: ``tpufw.workloads.serve._Server`` in-process, driven
over real HTTP on localhost by the benchmark's own open-loop client.

A run is two processes, one after the other, under a launcher that never
touches jax (a chip belongs to one process at a time):

1. ``serve``: weights on the device from the seed, the server as shipped,
   warm-up of every shape the cell's traffic uses, the ramp and the
   measured window, the reduction to metrics. It leaves a sample of the
   requests it finished for the next phase and exits, which is the one way
   to stop the server's scheduler and free the chip.
2. ``check``: the plain reference, alone on the chip, over that sample:
   how far below the reference's best logit each served token lies.

Each phase is a process group of its own that ends with the launcher,
however the launcher ends, and leaves by ``os._exit`` on every path
(``benchmarks/procs.py``).

The one substitution in the program: ``serve.build_generator`` is rebound
to hand the server the benchmark's weights (after the program's own
``_maybe_quantize`` and ``_maybe_unroll``). See PERF.md, "What must change
in the program".
"""

from __future__ import annotations

import json
import os
import random
import statistics
import subprocess
import sys
import time

from benchmarks import harness, procs, stats, traffic

TRACE_SECONDS = 6.0
#: The profiler starts this long before the request the stretch is anchored
#: to, and the stretch closes this long before the window does.
TRACE_LEAD = 0.25


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


# ------------------------------------------------------------ launcher


def scratch_dir(args) -> str:
    tag = "rehearse" if args.rehearse_cpu else "chip"
    d = os.path.join(harness.SCRATCH, f"{args.workload}.{args.seed}.{args.trace}.{tag}")
    os.makedirs(d, exist_ok=True)
    return d


def _phase_cmd(args, phase: str) -> list:
    cmd = [
        sys.executable, os.path.join(harness.HERE, "run.py"),
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", str(args.trace),
        "--phase", phase, "--t0", repr(args.t0),
    ]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    if args.control:
        cmd += ["--control", args.control]
    if args.broken:
        cmd += ["--break", args.broken]
    return cmd


def main(args, bench: dict, cell: dict, config: dict) -> int:
    phases = {"serve": serve_phase, "check": check_phase}
    if args.phase:
        # A phase holds the device, and after the serve phase's window the
        # scheduler's thread never ends and still drives it: every way out
        # leaves without the interpreter's teardown, which would race it.
        procs.exit_after(lambda: phases[args.phase](args, bench, cell, config))
    out = scratch_dir(args)
    for name in ("serve.json", "check.json", "served.json"):
        if os.path.exists(os.path.join(out, name)):
            os.remove(os.path.join(out, name))
    with procs.Launcher(harness.SCRATCH) as launcher:
        for phase in phases:
            rc = launcher.run(_phase_cmd(args, phase), harness.ROOT)
            if rc != 0:
                print(f"bench: the {phase} phase exited {rc}; no result", file=sys.stderr)
                return rc
    with open(os.path.join(out, "serve.json")) as f:
        served = json.load(f)
    with open(os.path.join(out, "check.json")) as f:
        checked = json.load(f)
    correct = bool(
        served["replies_ok"] and served["failed"] == 0
        and served["compiled_in_window"] == 0 and checked["within_limits"]
    )
    result = {
        "correct": correct,
        "attempted": served["attempted"],
        "failed": served["failed"],
        "metrics": served["metrics"],
        "device": served["device"],
    }
    if served.get("breakdown"):
        result["breakdown"] = served["breakdown"]
    if args.rehearse_cpu:
        result["rehearsal"] = "cpu, tiny widths: no number here is a measurement"
    # Each number that decided `correct` beside its limit: last in the line,
    # and the last lines on standard error.
    result["compared"] = {**served["compared"], **checked["compared"]}
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"bench: compare {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    return 0


# ------------------------------------------------------------ phase 1


def warmup_requests(reqs, env: dict) -> list:
    """(prompt length, max_new) pairs that make the server build every
    program this schedule can reach: one prefill-chunk program per tail
    width (the server keys them by page-granular width), the full chunk
    width, and the decode ladder's chunk lengths (powers of two up to
    TPUFW_SERVE_CHUNK)."""
    page = int(env["TPUFW_SERVE_PAGE"])
    chunk = int(env["TPUFW_SERVE_PREFILL_CHUNK"]) * page
    widths = set()
    for r in reqs:
        n = len(r.prompt)
        if n > chunk:
            widths.add(chunk)
        tail = n - ((n - 1) // chunk) * chunk
        widths.add(-(-tail // page) * page)
    ks, k = [], int(env["TPUFW_SERVE_CHUNK"])
    while k >= 1:
        ks.append(k)
        k //= 2
    widths = sorted(widths)
    n = max(len(widths), len(ks))
    return [(widths[i % len(widths)], ks[i % len(ks)] + 1) for i in range(n)]


def _device_json(jax, rehearse: bool) -> dict:
    dev = jax.local_devices()[0]
    st = dev.memory_stats() or {}
    out = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(st.get("peak_bytes_in_use", 0)),
    }
    if not rehearse:
        out["memory_limit_bytes"] = int(st.get("bytes_limit", 0))
    return out


def _parse_prom(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, val = line.rpartition(" ")
            try:
                out[name] = float(val)
            except ValueError:
                pass
    return out


def serve_phase(args, bench: dict, cell: dict, config: dict) -> int:
    import asyncio

    import jax

    rehearse = args.rehearse_cpu
    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(
            f"bench: need {cell['chips']} TPU chip(s), jax found {len(devices)} "
            f"{devices[0].platform} device(s); no result", file=sys.stderr,
        )
        return 3
    say(f"device platform={devices[0].platform} kind={devices[0].device_kind} count={len(devices)}"
        + (" (REHEARSAL on the CPU at tiny widths: no number below is a measurement)" if rehearse else ""))

    from benchmarks import client
    from benchmarks.compile_log import CompileLog
    from benchmarks.weights import make_weights

    if not rehearse:
        from tpufw.utils.profiling import enable_compile_cache

        say(f"compile cache at {enable_compile_cache()}")
    compiles = CompileLog()
    mix, keys, check = harness.cell_inputs(cell, config, rehearse)
    ref, adapter = harness.family_modules(config["family"])
    env = {k: str(v) for k, v in mix["server_env"].items()}
    if args.control == "int8_weights":
        # The control of `correct`: the program's own weight-only int8 path.
        env["TPUFW_QUANTIZE"] = "int8"
    os.environ.update(env)
    out_dir = scratch_dir(args)

    t = time.time()
    weights = make_weights(ref.weight_specs(keys), args.seed)
    model_cls, pc = adapter.program_model(keys, config["assumed"])
    params = adapter.to_program(weights, keys)
    del weights
    jax.block_until_ready(params)
    say(f"weights on the device from the seed in {time.time() - t:.1f} s")

    from tpufw.workloads import serve

    handed = [params]
    del params

    def build_generator():
        if args.control:
            # The program's own quantization, traced as one program over a
            # donated tree: run leaf by leaf it holds the bfloat16 tree, the
            # int8 tree and float32 temporaries of the largest leaf at once,
            # more than the chip has for the cell with the widest experts.
            made = {}

            def quantize(p):
                made["cfg"], q = serve._maybe_quantize(pc, p)
                return q

            p = jax.jit(quantize, donate_argnums=0)(handed.pop())
            cfg = made["cfg"]
        else:
            cfg, p = serve._maybe_quantize(pc, handed.pop())
        cfg, p = serve._maybe_unroll(cfg, p)
        return model_cls(cfg.decode_config()), p, cfg, False

    serve.build_generator = build_generator
    if args.broken == "token":
        # Test only: every token the pools sample comes out one id higher.
        from tpufw.infer import pages, sampling, slots

        def off_by_one(*a, **k):
            return (sampling.sample_token(*a, **k) + 1) % keys["vocab_size"]

        slots.sample_token = pages.sample_token = off_by_one
    import contextlib
    import io
    import threading

    t = time.time()
    with contextlib.redirect_stdout(io.StringIO()):
        srv = serve._Server(port=0, max_new_tokens=2)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        deadline = time.time() + 120
        while not hasattr(srv, "httpd") and time.time() < deadline:
            time.sleep(0.02)
    if not hasattr(srv, "httpd"):
        print("bench: the server's listener did not bind", file=sys.stderr)
        return 4
    host, port = "127.0.0.1", srv.port
    say(f"server up in {time.time() - t:.1f} s on port {port} with {env}")

    vocab = keys["vocab_size"]
    reqs = traffic.schedule(mix, args.seed, args.seconds, vocab)
    warm = warmup_requests(reqs, env)
    t = time.time()
    n_before = compiles.n
    wrng = random.Random(args.seed)
    for n_prompt, max_new in warm:
        wreq = traffic.Offered(0.0, tuple(wrng.randrange(1, vocab) for _ in range(n_prompt)), max_new)
        got = asyncio.run(client.drive(host, port, [wreq], 0.0, 900.0))["records"][0]
        if got["status"] != "ok" or len(got["tokens"]) != max_new:
            print(f"bench: warm-up request failed: {got.get('error', got['status'])}", file=sys.stderr)
            return 5
    say(f"warm-up: {len(warm)} requests (prompt, new) {warm} in {time.time() - t:.1f} s; "
        f"programs built so far {compiles.n} ({compiles.n - n_before} in warm-up), "
        f"cache hits {compiles.hits} misses {compiles.misses}")
    setup_s = time.time() - args.t0
    say(f"setup_s {setup_s:.3f}")

    _window(args, bench, cell, config, mix, keys, check, reqs, host, port,
            compiles, setup_s, out_dir, client, jax)
    return 0


def trace_offset(reqs, seconds: float):
    """(seconds from the window's opening at which the profiler starts, the
    request the stretch is anchored to or None). The stretch starts
    ``TRACE_LEAD`` before an arrival and closes ``TRACE_LEAD`` before the
    window does; of those starts, the one whose first half has the most
    prompt tokens due in it, the earliest on a tie: the stretch then holds
    an admission's prefill chunks and the decode steps beside and after
    them however fast the server is, where a stretch at mid-window holds
    whatever a slower tree was still doing then. It reads the schedule and nothing of the program,
    so it is the same for a parent and a change and for every seed (the mix
    fixes arrivals and lengths). A schedule with no such arrival is traced
    at mid-window."""
    best = None
    for r in sorted(reqs, key=lambda r: r.t):
        start = r.t - TRACE_LEAD
        if not 0.0 <= start <= seconds - TRACE_SECONDS - TRACE_LEAD:
            continue
        due = sum(len(q.prompt) for q in reqs if start <= q.t <= start + TRACE_SECONDS / 2.0)
        if best is None or due > best[0]:
            best = (due, start, r)
    if best is None:
        return max(0.0, seconds / 2.0 - TRACE_SECONDS / 2.0), None
    return best[1], best[2]


def _window(args, bench, cell, config, mix, keys, check, reqs, host, port,
            compiles, setup_s, out_dir, client, jax) -> None:
    import asyncio

    rehearse = args.rehearse_cpu
    seconds = float(args.seconds)
    digest = traffic.schedule_digest(reqs)
    in_win = [r for r in reqs if r.t >= 0]
    say(f"schedule digest {digest}: {len(reqs)} requests, {len(in_win)} due in the window "
        f"at {len(in_win) / seconds:.3f} req/s, ramp {mix.get('ramp_s', 0)} s; the mix fixes "
        f"arrivals and lengths, the seed the token ids and the weights")

    trace_dir = os.path.join(out_dir, "trace")
    traced = bool(args.trace) and not rehearse
    traced_from = traced_s = None
    # How long after the window the client still waits for first tokens.
    drain = float(mix["drain_s"])
    rec_path = os.path.join(out_dir, "records.json")
    cmd = [sys.executable, os.path.join(harness.HERE, "client.py"), "--port", str(port),
           "--workload", args.workload, "--seed", str(args.seed), "--seconds", str(seconds),
           "--drain", str(drain), "--out", rec_path]
    if rehearse:
        cmd.append("--rehearse-cpu")
    gen = subprocess.Popen(cmd, cwd=harness.ROOT, env=procs.child_env(), stdout=subprocess.PIPE, text=True)
    try:
        t0 = json.loads(gen.stdout.readline())["t0"]

        def until(offset):
            time.sleep(max(0.0, t0 + offset - time.time()))

        scrape = lambda: asyncio.run(client.http_get(host, port, "/metrics"))
        until(0.0)
        prom0, c0 = scrape(), compiles.n
        close = {}

        def close_window():
            until(seconds)
            close["prom1"], close["c1"] = scrape(), compiles.n
            close["device"] = _device_json(jax, rehearse)
            close["late_s"] = time.time() - (t0 + seconds)

        if traced:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
            offset, anchor = trace_offset(reqs, seconds)
            say(f"traced stretch: {TRACE_SECONDS:.0f} s from {offset:.2f} s of the window, "
                + (f"{TRACE_LEAD} s before the request due at {anchor.t:.2f} s ({len(anchor.prompt)} prompt tokens): "
                   f"of the arrivals it can start at, the one with the most prompt tokens due in its first half"
                   if anchor else "mid-window: no arrival of this schedule leaves the stretch room inside the window"))
            until(offset)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced_from = time.time()
            time.sleep(TRACE_SECONDS)
            traced_s = time.time() - traced_from
            # On the chip stop_trace returns 4 to 30 s after it is called, by the cell,
            # with the server running on meanwhile (PERF.md section 6, PR 35: on a thread
            # of its own it took 22 to 74 s), and a stretch may close a lead before the
            # window does: the window's close gets the thread, so that it is on time.
            import threading

            closing = threading.Thread(target=close_window, name="close_window")
            closing.start()
            jax.profiler.stop_trace()
            stopped_s = time.time() - traced_from - traced_s
            closing.join()
            say(f"the profiler took {stopped_s:.2f} s to stop; the window's second scrape was done "
                f"{close['late_s']:.2f} s after its close")
        else:
            close_window()
        prom1, c1, device = close["prom1"], close["c1"], close["device"]
        if gen.wait(timeout=drain + 120) != 0:
            raise RuntimeError("the load generator failed")
    finally:
        if gen.poll() is None:
            gen.kill()
            gen.wait()
    if args.broken == "raise":
        # Test only: the phase fails with the server up and the device held.
        raise RuntimeError("--break raise: the serve phase fails once its window has closed")
    with open(rec_path) as f:
        run = json.load(f)
    records, cutoff = run["records"], run["cutoff"]
    compiled_in_window = c1 - c0
    limits = mix.get("limits") or {}
    ws = stats.window_stats(records, t0, seconds, cutoff, limits, cell["chips"])

    # Every reply in the window: the requested count of in-vocabulary ids.
    vocab = keys["vocab_size"]
    bad = sum(
        1 for rec in records
        if any((not isinstance(x, int)) or x < 0 or x >= vocab for x in rec["tokens"])
        or len(rec["tokens"]) > rec["max_new"]
        or (rec["status"] == "ok" and len(rec["tokens"]) != rec["max_new"])
    )
    n_ok = sum(r["status"] == "ok" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    errors = sorted({r.get("error", "") for r in records if r["status"] == "error"})[:3]
    say(f"requests: {len(records)} offered, {n_ok} finished, {n_err} came back an error, "
        f"{len(records) - n_ok - n_err} cut {cutoff - t0 - seconds:.1f} s after the window; due in window "
        f"{ws['attempted']}, failed (an error, or no first token by the cut) {ws['failed']}"
        + (f"; errors {errors}" if errors else ""))
    late = ws.pop("late_ms")
    if late:
        say(f"generator lateness (sent - due): median {statistics.median(late):.3f} ms, "
            f"max {max(late):.3f} ms over {len(late)}")
    say(f"samples: ttft {ws['n_ttft']}, tpot {ws['n_tpot']} (requests with >= 16 tokens in the window): "
        f"medians are reported, the worst of each beside them")
    say(f"tokens: the requests due in the window asked for {ws['offered_tokens']} (offered_tokens) and had been sent "
        f"{ws['tokens_of_due']} of them when it closed (tokens_of_due: tokens_per_s_per_chip is this count over the window's "
        f"seconds); {ws['tokens_in_window']} tokens of every request, the ramp's too, arrived inside the window "
        f"(tokens_in_window: the rate's numerator until PR 43, in no metric now)")
    from benchmarks.metrics import _steps

    obs = {
        "records": records, "t0": t0, "seconds": seconds, "window": ws, "late_ms": late,
        "prom0": _parse_prom(prom0), "prom1": _parse_prom(prom1),
        "device": device, "family": config["family"], "config": keys,
        "trace": None, "rehearse": rehearse, "traced_from": traced_from, "traced_s": traced_s,
    }
    rows, cached = _steps.live_rows_and_tokens(obs)
    moment, what = _steps.sample_moment(obs)
    say(f"occupancy at {what} ({moment - t0:.2f} s of the window): {rows} of {mix['server_env']['TPUFW_SERVE_SLOTS']} slots decoding, "
        f"{cached} tokens in their caches; backlog {ws['backlog_start']} -> {ws['backlog_end']}")
    counts = ("attempted", "failed", "offered_tokens", "tokens_of_due", "tokens_in_window",
              "n_ttft", "n_tpot", "backlog_start", "backlog_end")
    say("window " + json.dumps({k: v for k, v in ws.items() if not rehearse or k in counts}))
    compared = {"requests_failed": ws["failed"], "replies_malformed": bad, "compiled_in_window": compiled_in_window}
    for name, value in compared.items():
        say(f"compare {name}={value} limit=0")
    if compiled_in_window:
        say(f"programs built in the window: {[n for n, _ in compiles.programs[c0:c1]]}")

    e2e = {}
    for m in harness.metrics_of(bench, cell["name"], "end_to_end"):
        if m["name"] == "setup_s":
            e2e["setup_s"] = {"value": setup_s, "unit": "s"}
        elif m["name"] in ws:
            e2e[m["name"]] = {"value": ws[m["name"]], "unit": m["unit"]}

    from benchmarks.metrics import _phases

    phases = _phases.deltas(obs)
    if phases:
        say(f"scheduler thread, self seconds by phase between the window's two scrapes ({seconds:.0f} s): "
            + json.dumps(dict(sorted(phases.items(), key=lambda kv: -kv[1]))))
    breakdown = None
    if traced:
        from benchmarks import trace_reduce

        planes = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        env = mix["server_env"]
        red = trace_reduce.reduce_trace(
            planes, cell["chips"], traced_s, keys["hidden_size"],
            int(env["TPUFW_SERVE_PREFILL_CHUNK"]) * int(env["TPUFW_SERVE_PAGE"]))
        obs["trace"] = red
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        say(f"traced stretch {red['window_s']:.3f} s (host clock between the profiler's start and stop "
            f"{traced_s:.3f} s, first to last device operation {red['span_s']:.3f} s)")
        say("programs in the trace: " + json.dumps(
            {k: {"n": v["n"], "seconds": v["seconds"], **({"tokens": v["tokens"]} if "tokens" in v else {})}
             for k, v in red["programs"].items()}))
    if args.trace:
        import importlib

        metrics = {}
        for m in harness.metrics_of(bench, cell["name"], "per_layer"):
            reader = importlib.import_module(harness.reader_module(m["name"]))
            value = reader.read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = e2e
    if rehearse:
        metrics = {}  # a CPU run reports no metric under a device metric's name
    else:
        say("end_to_end " + json.dumps(e2e))

    # A sample of the requests that were served tokens, the longest in it:
    # those that finished and those still running at the cut alike, since
    # long answers outlast the window and every served token can be checked.
    served = [r for r in records if r["status"] != "error" and len(r["tokens"]) >= 2]
    rng = random.Random(args.seed)
    served.sort(key=lambda r: -(r["n_prompt"] + len(r["tokens"])))
    pick = served[:1] + rng.sample(served[1:], max(0, min(len(served), check["sequences"]) - 1))
    sample = [{"prompt": list(reqs[r["i"]].prompt), "tokens": r["tokens"]} for r in pick]
    with open(os.path.join(out_dir, "served.json"), "w") as f:
        json.dump({"sample": sample, "served": len(served)}, f)
    with open(os.path.join(out_dir, "serve.json"), "w") as f:
        json.dump({
            "attempted": ws["attempted"], "failed": ws["failed"], "metrics": metrics,
            "device": device, "breakdown": breakdown, "replies_ok": bad == 0 and ws["attempted"] > 0,
            "compiled_in_window": compiled_in_window, "digest": digest,
            **{k: ws[k] for k in ("offered_tokens", "tokens_of_due", "tokens_in_window")},
            "compared": {k: {"value": v, "limit": 0} for k, v in compared.items()},
        }, f)


# ------------------------------------------------------------ phase 2


def check_phase(args, bench: dict, cell: dict, config: dict) -> int:
    """The plain reference over the served sample: for every served token,
    how far its reference logit lies below the reference's best
    (``gap_mean``, ``gap_max``) and, from which of the reference's near
    ties came out the other way, the program's logit noise
    (``stats.logit_noise``), each beside its limit. The gaps leave out the
    positions that the reference itself routed within ``routing_margin`` of
    a tie: there a sound computation may take the other expert and land far
    from the reference's token (PERF.md §2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rehearse = args.rehearse_cpu
    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        print("bench: the check phase found no TPU; no result", file=sys.stderr)
        return 3
    if not rehearse:
        from tpufw.utils.profiling import enable_compile_cache

        enable_compile_cache()
    from benchmarks.reference import common
    from benchmarks.weights import make_weights

    out_dir = scratch_dir(args)
    with open(os.path.join(out_dir, "served.json")) as f:
        served = json.load(f)
    mix, keys, check = harness.cell_inputs(cell, config, rehearse)
    ref, _ = harness.family_modules(config["family"])
    t = time.time()
    weights = make_weights(ref.weight_specs(keys), args.seed)
    fwd = jax.jit(lambda w, toks, at: ref.logits(w, keys, toks, at))
    block = common.QUERY_BLOCK

    if not served["sample"]:
        say("no request was served two tokens: nothing to compare with the reference, so not correct")
        with open(os.path.join(out_dir, "check.json"), "w") as f:
            json.dump({"within_limits": False, "compared": {"sequences_to_check": {"value": 0, "limit": "1 or more"}}}, f)
        return 0
    gaps, margins, top2 = [], [], []
    for s in served["sample"]:
        # Padded to the attention block times a power of two, so a cell
        # compiles a handful of lengths; padding follows the real tokens
        # and cannot reach them through the causal mask.
        n = len(s["tokens"])
        t_pad = block
        while t_pad < len(s["prompt"]) + n:
            t_pad *= 2
        seq = (s["prompt"] + s["tokens"][:-1] + [0] * t_pad)[:t_pad]
        at = ([len(s["prompt"]) - 1 + j for j in range(n)] + [0] * t_pad)[:t_pad]
        lg, margin = fwd(weights, jnp.asarray(seq, jnp.int32), jnp.asarray(at, jnp.int32))
        lg = lg[:n]
        best2 = jax.lax.top_k(lg, 2)[0]
        gaps.append(np.asarray(best2[:, 0] - lg[jnp.arange(n), jnp.asarray(s["tokens"])]))
        top2.append(np.asarray(best2[:, 0] - best2[:, 1]))
        margins.append(np.asarray(margin[:n]))
    all_gaps, margins, top2 = np.concatenate(gaps), np.concatenate(margins), np.concatenate(top2)
    got = stats.gap_numbers(all_gaps.tolist(), top2.tolist(), margins.tolist(), check["routing_margin"])
    say(f"reference over {len(served['sample'])} of {served['served']} requests that were served tokens, "
        f"{all_gaps.size} served tokens in {time.time() - t:.1f} s: logit_noise over all of them, the gaps over "
        f"{got['tokens']} ({got['left_out']} routed within {check['routing_margin']} of a tie are left out, "
        f"{100 * got['moved_share']:.2f}% of the rest are not the reference's first choice)")
    numbers = ("logit_noise", "gap_max", "gap_mean")
    for k in numbers:
        say(f"compare {k}={got[k]:.6f} limit={check[k]}")
    within = all(got[k] <= check[k] for k in numbers)
    with open(os.path.join(out_dir, "check.json"), "w") as f:
        json.dump({"within_limits": bool(within), **got,
                   "compared": {k: {"value": got[k], "limit": check[k]} for k in numbers},
                   # Per position, for whoever sets the limits anew: the served token's gap,
                   # the reference's own top-two margin and its routing margin.
                   "positions": {"gap": all_gaps.round(5).tolist(), "top2": top2.round(5).tolist(),
                                 "routing_margin": np.minimum(margins, 9.0).round(5).tolist()}}, f)
    return 0
