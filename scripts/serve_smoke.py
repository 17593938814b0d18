"""CI smoke for the slot scheduler's continuous batching.

Starts the CPU HTTP server (llama3_tiny, random init — weight values
don't matter for scheduling behavior), then overlaps three requests:

- LONG:   max_new 600 — submitted first, holds a slot the whole run;
- SHORT:  max_new 4  — submitted after the long one has started;
- STREAM: max_new 16 — SSE, sharing decode chunks with both.

The assertion that matters: the SHORT request COMPLETES while the
LONG one is still decoding. Under the old tick batcher this is
impossible (the short rows ride the tick to the long request's
bucketed max_new, or wait for the solo stream tick); under the slot
scheduler the short row joins mid-flight and retires at its own
max_new. TPUFW_SERVE_CHUNK=2 keeps chunk boundaries (= join/retire
opportunities) frequent on a tiny model.

The run uses the PAGED KV pool (TPUFW_SERVE_PAGE=16): after the
overlap test, two sequential requests share a 36-token prefix — the
second must hit the prefix cache (tpufw_serve_prefix_hits_total >= 1
on /metrics), and by the end retired rows must have returned pages
to the arena (pages_freed_total > 0, pages_in_use < pages_total).

Speculation rides the whole smoke: TPUFW_SERVE_SPEC_K=4 turns on
n-gram self-drafting for every request above (greedy verify is
bit-exact, so the length/ordering assertions double as a parity
check), and a final section runs one more request end-to-end and
asserts the spec metrics are exposed on /metrics.

Chunked prefill rides the whole smoke too (TPUFW_SERVE_PREFILL_CHUNK
=1: every admission drains page-by-page through the shared passes —
chunked-vs-monolithic is bit-equal under greedy, so every assertion
above doubles as a parity check), and a final section submits a
1-page prompt AFTER a 6-page prompt and asserts the short request's
first streamed token lands BEFORE the long one's — a long prompt no
longer head-of-line-blocks admission.

Exit 0 on success; any assertion or HTTP failure exits nonzero.
"""

import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
)
os.environ.setdefault("TPUFW_MODEL", "llama3_tiny")
# Room for a LONG request that is still decoding when the others arrive:
# the tiny model on one CPU device emits a 60-token answer in ~0.1 s.
os.environ.setdefault("TPUFW_MAX_SEQ_LEN", "1024")
os.environ.setdefault("TPUFW_SERVE_CHUNK", "2")
os.environ.setdefault("TPUFW_SERVE_PAGE", "16")
os.environ.setdefault("TPUFW_SERVE_SPEC_K", "4")
os.environ.setdefault("TPUFW_SERVE_PREFILL_CHUNK", "1")

LONG_NEW, SHORT_NEW, STREAM_NEW = 600, 4, 16


def main() -> int:
    from tpufw.workloads.serve import _Server

    srv = _Server(port=0, max_new_tokens=LONG_NEW)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    deadline = time.time() + 60
    while not hasattr(srv, "httpd") and time.time() < deadline:
        time.sleep(0.05)
    base = f"http://127.0.0.1:{srv.port}"

    done_at: dict[str, float] = {}
    errors: list[str] = []

    def post(name: str, body: dict) -> None:
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=600) as resp:
                out = json.loads(resp.read())
            assert len(out["outputs"][0]) == body["max_new_tokens"], out
        except Exception as e:  # noqa: BLE001 — report, don't hang CI
            errors.append(f"{name}: {type(e).__name__}: {e}")
        done_at[name] = time.time()

    def post_stream(name: str, body: dict) -> None:
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            events = []
            with urllib.request.urlopen(req, timeout=600) as resp:
                for line in resp:
                    line = line.strip()
                    if line.startswith(b"data: "):
                        events.append(json.loads(line[len(b"data: "):]))
            chunks = [e["outputs"] for e in events if "outputs" in e]
            # chunk 2 over 16 tokens: it must have actually streamed.
            assert len(chunks) >= 2, events
            assert events[-1] == {"done": True}, events
            got = sum(len(r) for rows in chunks for r in rows)
            assert got == body["max_new_tokens"], (got, events)
        except Exception as e:  # noqa: BLE001
            errors.append(f"{name}: {type(e).__name__}: {e}")
        done_at[name] = time.time()

    long_t = threading.Thread(
        target=post,
        args=("long", {"prompts": [[1, 2, 3]], "max_new_tokens": LONG_NEW}),
    )
    long_t.start()
    # Let the long request occupy its slot first: wait until the
    # scheduler reports it, not for a guessed interval.
    deadline = time.time() + 30
    while time.time() < deadline:
        with urllib.request.urlopen(base + "/metrics", timeout=30) as resp:
            if b"tpufw_serve_slots_occupied 1" in resp.read():
                break
        time.sleep(0.01)
    short_t = threading.Thread(
        target=post,
        args=("short", {"prompts": [[4, 5]], "max_new_tokens": SHORT_NEW}),
    )
    stream_t = threading.Thread(
        target=post_stream,
        args=(
            "stream",
            {
                "prompts": [[6, 7, 8]],
                "max_new_tokens": STREAM_NEW,
                "stream": True,
            },
        ),
    )
    short_t.start()
    stream_t.start()
    for t in (long_t, short_t, stream_t):
        t.join(timeout=600)

    if errors:
        print("serve-smoke FAILED:\n  " + "\n  ".join(errors))
        return 1
    order = sorted(done_at, key=done_at.get)
    print(
        "completion order:",
        " -> ".join(f"{n}@{done_at[n] - min(done_at.values()):.2f}s"
                    for n in order),
    )
    if done_at["short"] >= done_at["long"]:
        print(
            "serve-smoke FAILED: short request did not complete before "
            "the long one — continuous batching is not interleaving"
        )
        return 1
    print("serve-smoke OK: short joined and retired mid-flight")

    # ---- paged KV: prefix sharing + page reclamation ----
    from tpufw.workloads.env import env_int

    if not env_int("serve_page", 0):
        print("serve-smoke: paged-KV section skipped (TPUFW_SERVE_PAGE=0)")
        srv.httpd.shutdown()
        return 0
    # Sequential on purpose: the second request must be admitted after
    # the first registered its prompt pages in the trie.
    shared = list(range(40, 76))  # 36 tokens = 2 full 16-token pages
    post("prefix_a", {"prompts": [shared + [7, 9]], "max_new_tokens": 8})
    post("prefix_b", {"prompts": [shared + [11, 3]], "max_new_tokens": 8})
    if errors:
        print("serve-smoke FAILED:\n  " + "\n  ".join(errors))
        return 1
    with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
        metrics = {}
        for line in resp.read().decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.partition(" ")
                metrics[name] = float(val)
    hits = metrics.get("tpufw_serve_prefix_hits_total", 0.0)
    freed = metrics.get("tpufw_serve_pages_freed_total", 0.0)
    in_use = metrics.get("tpufw_serve_pages_in_use", -1.0)
    total = metrics.get("tpufw_serve_pages_total", 0.0)
    print(
        f"paged KV: prefix_hits={hits:.0f} pages_freed={freed:.0f} "
        f"pages_in_use={in_use:.0f}/{total:.0f}"
    )
    if hits < 1:
        print("serve-smoke FAILED: no prefix cache hit on the shared "
              "36-token prefix")
        return 1
    if freed <= 0 or not (0 <= in_use < total):
        print("serve-smoke FAILED: retired rows did not return pages "
              "to the arena")
        return 1
    print("serve-smoke OK: prefix shared and pages reclaimed")

    # ---- speculative decoding: one request end-to-end + metrics ----
    from tpufw.workloads.env import env_int as _env_int

    if not _env_int("serve_spec_k", 0):
        print("serve-smoke: spec section skipped (TPUFW_SERVE_SPEC_K=0)")
        srv.httpd.shutdown()
        return 0
    # A self-similar prompt gives the n-gram draft something to mine;
    # whatever it accepts, greedy verify keeps the output exact.
    post(
        "spec",
        {"prompts": [[5, 9, 5, 9, 5, 9, 5, 9, 5, 9]],
         "max_new_tokens": 12},
    )
    if errors:
        print("serve-smoke FAILED:\n  " + "\n  ".join(errors))
        return 1
    with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
        metrics = {}
        for line in resp.read().decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.partition(" ")
                metrics[name] = float(val)
    missing = [
        n for n in (
            "tpufw_spec_accept_rate",
            "tpufw_spec_fallback_slots",
            "tpufw_spec_wasted_draft_flops_total",
        ) if n not in metrics
    ]
    if missing:
        print(f"serve-smoke FAILED: spec metrics absent: {missing}")
        return 1
    print(
        "spec: accept_rate="
        f"{metrics['tpufw_spec_accept_rate']:.3f} "
        f"fallback_slots={metrics['tpufw_spec_fallback_slots']:.0f} "
        "wasted_draft_flops="
        f"{metrics['tpufw_spec_wasted_draft_flops_total']:.0f}"
    )
    print("serve-smoke OK: speculative request served end-to-end")

    # ---- chunked prefill: no head-of-line blocking on admission ----
    if not env_int("serve_prefill_chunk", 0):
        print("serve-smoke: chunked-prefill section skipped "
              "(TPUFW_SERVE_PREFILL_CHUNK=0)")
        srv.httpd.shutdown()
        return 0
    # A 6-page prompt submitted FIRST, a 1-page prompt AFTER it: with
    # chunked admission the short prompt's single prefill chunk
    # interleaves between the long one's six, so its first streamed
    # token must land before the long prompt even finishes prefilling
    # (and therefore before the long one's first token).
    first_chunk_at: dict[str, float] = {}

    def post_stream_timed(name: str, body: dict) -> None:
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=600) as resp:
                for line in resp:
                    line = line.strip()
                    if not line.startswith(b"data: "):
                        continue
                    ev = json.loads(line[len(b"data: "):])
                    if (
                        name not in first_chunk_at
                        and any(ev.get("outputs") or [])
                    ):
                        first_chunk_at[name] = time.time()
        except Exception as e:  # noqa: BLE001
            errors.append(f"{name}: {type(e).__name__}: {e}")

    long_prompt = list(range(2, 98))  # 96 tokens = 6 pages
    hol_long = threading.Thread(
        target=post_stream_timed,
        args=("hol_long", {
            "prompts": [long_prompt], "max_new_tokens": 12,
            "stream": True,
        }),
    )
    hol_long.start()
    time.sleep(0.05)  # long admission grabs its slot first
    hol_short = threading.Thread(
        target=post_stream_timed,
        args=("hol_short", {
            "prompts": [[9, 8, 7, 6, 5, 4, 3, 2]], "max_new_tokens": 4,
            "stream": True,
        }),
    )
    hol_short.start()
    hol_long.join(timeout=600)
    hol_short.join(timeout=600)
    if errors:
        print("serve-smoke FAILED:\n  " + "\n  ".join(errors))
        return 1
    if not ("hol_long" in first_chunk_at and "hol_short" in first_chunk_at):
        print(f"serve-smoke FAILED: missing first tokens "
              f"({sorted(first_chunk_at)})")
        return 1
    gap = first_chunk_at["hol_long"] - first_chunk_at["hol_short"]
    print(f"chunked prefill: short first token {gap:.3f}s before long's")
    if gap <= 0:
        print("serve-smoke FAILED: 1-page prompt head-of-line blocked "
              "behind the 6-page prompt's prefill")
        return 1
    with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
        metrics = {}
        for line in resp.read().decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.partition(" ")
                metrics[name] = float(val)
    chunks = metrics.get("tpufw_prefill_chunks_total", 0.0)
    inflight = metrics.get("tpufw_prefill_inflight", -1.0)
    if chunks < 7 or "tpufw_prefill_resumes_total" not in metrics \
            or inflight != 0:
        print(f"serve-smoke FAILED: chunked-prefill series wrong "
              f"(chunks={chunks}, inflight={inflight}, "
              f"resumes_present="
              f"{'tpufw_prefill_resumes_total' in metrics})")
        return 1
    print(f"serve-smoke OK: chunked prefill interleaved "
          f"({chunks:.0f} chunks, no HOL blocking)")
    srv.httpd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
