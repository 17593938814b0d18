"""The benchmark's own streaming client: one thread, one event loop, one
connection per request, each sent when it is due whatever the server is
doing (open loop) and timed on this clock from the moment it was due.

The measured window's load comes from a process of its own (``main``
below, started by the serve phase, and killed by the kernel should that
phase die), so that the generator never waits for the interpreter lock of
the server it is loading. Times are wall-clock
seconds, shared with the serve phase's scrapes and profiler."""

from __future__ import annotations

import asyncio
import json
import sys
import time


async def _one(host: str, port: int, req, due: float, rec: dict) -> None:
    delay = due - time.time()
    if delay > 0:
        await asyncio.sleep(delay)
    body = json.dumps(
        {"prompts": [list(req.prompt)], "max_new_tokens": req.max_new, "stream": True}
    ).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(
            b"POST /generate HTTP/1.1\r\nHost: bench\r\nContent-Type: application/json\r\n"
            b"Connection: close\r\nContent-Length: " + str(len(body)).encode() + b"\r\n\r\n" + body
        )
        await writer.drain()
        rec["sent"] = time.time()
        status = await reader.readline()
        if b" 200 " not in status:
            rec["status"], rec["error"] = "error", status.decode(errors="replace").strip()
            return
        while True:
            line = await reader.readline()
            if not line:
                rec["status"], rec["error"] = "error", "stream ended without a done event"
                return
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[6:])
            if "outputs" in ev:
                row = ev["outputs"][0]
                if row:
                    rec["chunks"].append((time.time(), len(row)))
                    rec["tokens"].extend(row)
            elif ev.get("done"):
                rec["status"], rec["done"] = "ok", time.time()
                return
            elif "error" in ev:
                rec["status"], rec["error"] = "error", str(ev["error"])
                return
    except asyncio.CancelledError:
        rec["status"] = "cut"
        raise
    except OSError as e:
        rec["status"], rec["error"] = "error", f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def http_get(host: str, port: int, path: str) -> str:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: bench\r\nConnection: close\r\n\r\n".encode())
        await writer.drain()
        raw = await reader.read()
    finally:
        writer.close()
    return raw.split(b"\r\n\r\n", 1)[1].decode()


async def drive(host: str, port: int, reqs, seconds: float, drain_s: float, announce=None,
                until: str = "done") -> dict:
    """Offer ``reqs`` (``t`` relative to the window's opening; the ramp is
    negative). After the window closes, wait at most ``drain_s`` longer:
    ``until="done"`` for every request to finish, ``until="first"`` for
    every request to have its first token or its error. Then cut what is
    left: a request with no token by then was not answered. ``announce``
    is told the window's opening time before the first request is due."""
    lead = max(0.0, -min((r.t for r in reqs), default=0.0))
    t0 = time.time() + lead + 0.5
    if announce is not None:
        announce(t0)
    records = [
        {"i": i, "due": t0 + r.t, "sent": None, "done": None, "chunks": [], "tokens": [],
         "status": "pending", "n_prompt": len(r.prompt), "max_new": r.max_new}
        for i, r in enumerate(reqs)
    ]
    tasks = [
        asyncio.create_task(_one(host, port, r, rec["due"], rec))
        for r, rec in zip(reqs, records)
    ]
    await asyncio.sleep(max(0.0, t0 + seconds - time.time()))
    give_up = time.time() + drain_s
    if until == "done":
        if drain_s > 0 and tasks:
            await asyncio.wait(tasks, timeout=drain_s)
    else:
        while time.time() < give_up and any(
            rec["status"] == "pending" and not rec["chunks"] for rec in records
        ):
            await asyncio.sleep(0.05)
    cutoff = time.time()
    for t in tasks:
        if not t.done():
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for rec in records:
        if rec["status"] == "pending":
            rec["status"] = "cut"
    return {"records": records, "t0": t0, "cutoff": cutoff}


def main(argv=None) -> int:
    """The load generator as a process: builds the cell's schedule from
    the seed, announces the window's opening on its first output line,
    drives it, and writes the records to ``--out``."""
    import argparse

    from benchmarks import harness, procs, traffic

    procs.die_with_parent()
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drain", type=float, required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell(bench, a.workload)
    config = harness.load_json(harness.config_entry(bench, cell["config"])["file"])
    mix, keys, _ = harness.cell_inputs(cell, config, a.rehearse_cpu)
    reqs = traffic.schedule(mix, a.seed, a.seconds, keys["vocab_size"])

    def announce(t0):
        print(json.dumps({"t0": t0}), flush=True)

    run = asyncio.run(drive("127.0.0.1", a.port, reqs, a.seconds, a.drain, announce, until="first"))
    with open(a.out, "w") as f:
        json.dump(run, f)
    return 0


if __name__ == "__main__":
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.exit(main())
