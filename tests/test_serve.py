"""Serving workload: checkpoint restore -> batch generate, and HTTP mode.

Covers the 07-infer manifest's code path (VERDICT r1 item 9): a checkpoint
written by the Trainer is loaded by tpufw.workloads.serve, generation is
deterministic (greedy), and the HTTP server answers /generate + /healthz.
"""

import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from tpufw.mesh import MeshConfig
from tpufw.models import LLAMA_CONFIGS, Llama
from tpufw.train import Trainer, TrainerConfig, synthetic_batches


@pytest.fixture()
def tiny_env(tmp_path, monkeypatch):
    """Train llama3_tiny for 2 steps, checkpoint it, point TPUFW_* at it."""
    ckpt = str(tmp_path / "ckpt")
    cfg = LLAMA_CONFIGS["llama3_tiny"]
    trainer = Trainer(
        Llama(cfg),
        TrainerConfig(
            batch_size=8,  # divides the 8-device fsdp test mesh
            seq_len=16,
            total_steps=2,
            lr=1e-3,
            checkpoint_dir=ckpt,
            checkpoint_every=1,
        ),
        MeshConfig(),
    )
    trainer.init_state()
    trainer.run(
        synthetic_batches(8, 16, cfg.vocab_size),
        model_flops_per_token=cfg.flops_per_token(15),
    )
    monkeypatch.setenv("TPUFW_MODEL", "llama3_tiny")
    monkeypatch.setenv("TPUFW_CHECKPOINT_DIR", ckpt)
    monkeypatch.setenv("TPUFW_MAX_NEW_TOKENS", "4")
    return cfg, trainer


def test_batch_generate_restores_checkpoint(tiny_env):
    from tpufw.workloads.serve import run_batch

    cfg, trainer = tiny_env
    results = run_batch([[1, 5, 9], [2]], max_new_tokens=4)
    assert len(results) == 2
    for r in results:
        assert r["restored_checkpoint"] is True
        assert len(r["output"]) == 4
        assert all(0 <= t < cfg.vocab_size for t in r["output"])

    # Greedy generation from the restored params must equal generation
    # from the in-memory trained params: restore really round-tripped.
    from tpufw.infer import SamplingConfig, generate_text

    want = generate_text(
        Llama(cfg.decode_config()),
        trainer.state.params,
        [[1, 5, 9]],
        max_new_tokens=4,
        sampling=SamplingConfig(temperature=0.0),
    )[0]
    assert results[0]["output"] == want


def test_batch_generate_unrolled_matches_scanned(tiny_env, monkeypatch):
    """The unrolled default serves the unscanned twin from the SAME
    scanned checkpoint with identical greedy outputs as the scanned
    path — the whole env -> build_generator -> unstack -> generate
    path. The scanned baseline is pinned with TPUFW_DECODE_UNROLL=0
    (unroll is the serving default since the r5 hardware measurement);
    the unrolled run relies on the default, covering it."""
    from tpufw.workloads.serve import run_batch

    prompts = [[1, 5, 9], [2]]
    monkeypatch.setenv("TPUFW_DECODE_UNROLL", "0")
    want = run_batch(prompts, max_new_tokens=4)
    monkeypatch.delenv("TPUFW_DECODE_UNROLL")
    got = run_batch(prompts, max_new_tokens=4)
    assert [r["output"] for r in got] == [r["output"] for r in want]


def test_batch_generate_without_checkpoint(monkeypatch, tmp_path):
    from tpufw.workloads.serve import run_batch

    monkeypatch.setenv("TPUFW_MODEL", "llama3_tiny")
    monkeypatch.setenv("TPUFW_CHECKPOINT_DIR", str(tmp_path / "empty"))
    results = run_batch([[3, 1, 4]], max_new_tokens=3)
    assert results[0]["restored_checkpoint"] is False
    assert len(results[0]["output"]) == 3


def test_http_server_generate(tiny_env):
    from tpufw.workloads.serve import _Server

    srv = _Server(port=0, max_new_tokens=4)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    # serve_forever resolves port 0 before printing its banner; poll until
    # the listener is up.
    import time

    deadline = time.time() + 30
    while not hasattr(srv, "httpd") and time.time() < deadline:
        time.sleep(0.05)
    base = f"http://127.0.0.1:{srv.port}"

    with urllib.request.urlopen(base + "/healthz", timeout=30) as resp:
        health = json.loads(resp.read())
    assert health["ok"] is True

    req = urllib.request.Request(
        base + "/generate",
        data=json.dumps(
            {"prompts": [[1, 5, 9], [2, 7]], "max_new_tokens": 3}
        ).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        out = json.loads(resp.read())
    assert len(out["outputs"]) == 2
    assert all(len(o) == 3 for o in out["outputs"])

    # Text prompts (byte codec default): encoded server-side, outputs
    # decoded back to text alongside the raw ids.
    treq = urllib.request.Request(
        base + "/generate",
        data=json.dumps({"texts": ["hi", "ok"], "max_new_tokens": 3}).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(treq, timeout=120) as resp:
        tout = json.loads(resp.read())
    assert len(tout["outputs"]) == 2 and len(tout["texts"]) == 2
    assert all(isinstance(s, str) for s in tout["texts"])

    # Bad request -> 400 with an error body, server stays up.
    for bad_body in (
        {"prompts": "nope"},
        {"texts": [""]},
        {"texts": "hello"},  # bare string must not iterate as chars
    ):
        bad = urllib.request.Request(
            base + "/generate",
            data=json.dumps(bad_body).encode(),
            method="POST",
        )
        try:
            urllib.request.urlopen(bad, timeout=30)
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
    srv.httpd.shutdown()


def test_http_server_streaming(tiny_env, monkeypatch):
    """SSE streaming: chunk events carry per-row NEW token ids whose
    concatenation equals the non-streamed greedy output exactly; the
    final event carries done (and full texts for text requests); a
    sampled stream also round-trips. Chunk size 2 forces multiple
    events for a 6-token request."""
    import time

    from tpufw.workloads.serve import _Server

    monkeypatch.setenv("TPUFW_STREAM_CHUNK", "2")
    srv = _Server(port=0, max_new_tokens=8)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    deadline = time.time() + 30
    while not hasattr(srv, "httpd") and time.time() < deadline:
        time.sleep(0.05)
    base = f"http://127.0.0.1:{srv.port}"

    def post(body):
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        return urllib.request.urlopen(req, timeout=300)

    def read_events(resp):
        events = []
        for line in resp:
            line = line.strip()
            if line.startswith(b"data: "):
                events.append(json.loads(line[len(b"data: "):]))
        return events

    prompts = [[1, 5, 9], [2, 7]]
    with post({"prompts": prompts, "max_new_tokens": 6}) as resp:
        want = json.loads(resp.read())["outputs"]
    with post(
        {"prompts": prompts, "max_new_tokens": 6, "stream": True}
    ) as resp:
        assert resp.headers["Content-Type"].startswith(
            "text/event-stream"
        )
        events = read_events(resp)
    chunks = [e["outputs"] for e in events if "outputs" in e]
    assert len(chunks) >= 3  # 6 tokens / chunk 2: it actually streamed
    got = [[] for _ in prompts]
    for rows in chunks:
        for acc, r in zip(got, rows):
            acc.extend(r)
    assert got == want
    assert events[-1] == {"done": True}

    # Text request: chunk events stream ids, the final event decodes.
    with post(
        {"texts": ["hi", "yo"], "max_new_tokens": 6, "stream": True}
    ) as resp:
        tevents = read_events(resp)
    assert tevents[-1]["done"] is True
    assert len(tevents[-1]["texts"]) == 2
    assert all(isinstance(s, str) for s in tevents[-1]["texts"])

    # Sampled stream serves end-to-end too (fresh tick seed per tick).
    with post(
        {
            "prompts": prompts,
            "max_new_tokens": 6,
            "temperature": 100.0,
            "stream": True,
        }
    ) as resp:
        sevents = read_events(resp)
    sgot = [[] for _ in prompts]
    for rows in (e["outputs"] for e in sevents if "outputs" in e):
        for acc, r in zip(sgot, rows):
            acc.extend(r)
    assert all(len(r) == 6 for r in sgot)
    assert sgot != want  # near-uniform sampling differs from greedy
    srv.httpd.shutdown()


def test_http_server_openai_compat(tiny_env):
    """`/v1/completions` speaks the OpenAI completions shape: string /
    token-list prompts, max_tokens, choices with text + finish_reason,
    usage accounting; outputs equal the native endpoint's for the same
    prompt; unsupported OpenAI knobs 400 with the alternative named."""
    import time

    from tpufw.workloads.serve import _Server

    srv = _Server(port=0, max_new_tokens=8)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    deadline = time.time() + 30
    while not hasattr(srv, "httpd") and time.time() < deadline:
        time.sleep(0.05)
    base = f"http://127.0.0.1:{srv.port}"

    def post(path, body):
        req = urllib.request.Request(
            base + path,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read())

    native = post(
        "/generate", {"texts": ["hi"], "max_new_tokens": 4}
    )
    out = post(
        "/v1/completions",
        {"model": "tpufw-test", "prompt": "hi", "max_tokens": 4},
    )
    assert out["object"] == "text_completion"
    assert out["model"] == "tpufw-test"
    assert out["choices"][0]["text"] == native["texts"][0]
    assert out["choices"][0]["finish_reason"] == "length"
    assert out["usage"]["completion_tokens"] == 4
    assert (
        out["usage"]["total_tokens"]
        == out["usage"]["prompt_tokens"] + 4
    )

    # Token-list prompt form; text still decoded in the response.
    tok = post(
        "/v1/completions", {"prompt": [1, 5, 9], "max_tokens": 4}
    )
    assert len(tok["choices"]) == 1
    assert isinstance(tok["choices"][0]["text"], str)

    # Unsupported knobs 400 loudly with the alternative named.
    for bad in (
        {"prompt": "hi", "stream": True},
        {"prompt": "hi", "n": 2},
        {"max_tokens": 4},  # no prompt
    ):
        req = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps(bad).encode(),
            method="POST",
        )
        try:
            urllib.request.urlopen(req, timeout=30)
            raise AssertionError(f"expected 400 for {bad}")
        except urllib.error.HTTPError as e:
            assert e.code == 400
    srv.httpd.shutdown()


def test_sampling_env_resolution(clear_tpufw_env):
    clear_tpufw_env.setenv("TPUFW_TEMPERATURE", "0.7")
    clear_tpufw_env.setenv("TPUFW_TOP_K", "40")
    clear_tpufw_env.setenv("TPUFW_MIN_P", "0.05")
    clear_tpufw_env.setenv("TPUFW_REPETITION_PENALTY", "1.2")

    from tpufw.workloads.serve import sampling_from_env

    s = sampling_from_env()
    assert s.temperature == 0.7 and s.top_k == 40
    assert s.top_p is None and s.min_p == 0.05
    assert s.repetition_penalty == 1.2


def test_sampling_env_defaults_greedy(clear_tpufw_env):
    from tpufw.workloads.serve import sampling_from_env

    s = sampling_from_env()
    assert s.temperature == 0.0
    assert s.top_k is None and s.top_p is None and s.min_p is None
    assert s.repetition_penalty is None


def test_http_server_continuous_batching(tiny_env, monkeypatch):
    """VERDICT r2 #7: concurrent clients coalesce into one device tick
    instead of serializing with full per-request latency. Pinned three
    ways: (a) concurrent wall-clock beats the same requests run
    sequentially, (b) at least one response reports batched_with >= 2,
    (c) greedy outputs are identical coalesced vs alone (batch
    composition must not leak between rows)."""
    import time

    from tpufw.workloads.serve import _Server

    # A wide coalescing window makes the tick grouping deterministic.
    monkeypatch.setenv("TPUFW_BATCH_WAIT_MS", "100")
    srv = _Server(port=0, max_new_tokens=4)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    deadline = time.time() + 30
    while not hasattr(srv, "httpd") and time.time() < deadline:
        time.sleep(0.05)
    base = f"http://127.0.0.1:{srv.port}"

    def post(prompts, max_new=16):
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps(
                {"prompts": prompts, "max_new_tokens": max_new}
            ).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read())

    prompts = [[1, 5, 9], [2, 7], [3], [4, 4, 4, 4]]
    # Warm both compiled shapes: the coalesced 4-row tick and the
    # single-request tick (compile time must not pollute the timing).
    post(prompts)
    post([prompts[0]])

    t0 = time.perf_counter()
    seq_outs = [post([p])["outputs"][0] for p in prompts]
    t_seq = time.perf_counter() - t0

    results: dict[int, dict] = {}

    def worker(i):
        results[i] = post([prompts[i]])

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(4)
    ]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    t_conc = time.perf_counter() - t0

    assert len(results) == 4
    batched = [r["batched_with"] for r in results.values()]
    assert max(batched) >= 2, f"no coalescing happened: {batched}"
    # (c) same greedy tokens coalesced vs alone.
    for i in range(4):
        assert results[i]["outputs"][0] == seq_outs[i], i
    # (a) concurrent < sequential wall-clock (same warm shapes). The
    # 0.1s coalescing window is included; margin keeps CI honest but
    # not flaky.
    assert t_conc < t_seq * 0.9 + 0.2, (t_conc, t_seq)

    # Prometheus /metrics (the serving analog of the device plugin's
    # endpoint): counters reflect the traffic this test just drove.
    with urllib.request.urlopen(base + "/metrics", timeout=30) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    metrics = {
        ln.split()[0]: float(ln.split()[1])
        for ln in text.splitlines()
        if ln and not ln.startswith("#")
    }
    # 2 warmups + 4 sequential + 4 concurrent = 10 requests, 0 errors —
    # and the zero-valued error counter is PRESENT (pre-initialized),
    # so absent-series alerts can't misfire.
    assert metrics["tpufw_serve_requests_total"] == 10
    assert metrics["tpufw_serve_request_errors_total"] == 0
    # Coalescing means fewer ticks than requests; every request's rows
    # were served.
    assert metrics["tpufw_serve_ticks_total"] < 10
    assert metrics["tpufw_serve_tick_rows_total"] >= 10
    assert metrics["tpufw_serve_tokens_generated_total"] > 0
    assert metrics["tpufw_serve_request_seconds_total"] > 0
    assert "tpufw_serve_queue_depth" in metrics
    srv.httpd.shutdown()


def test_http_server_per_request_sampling(tiny_env, monkeypatch):
    """Requests may carry their own temperature/top-k/top-p: sampled
    output differs from greedy, explicit-default requests still
    coalesce with default traffic, and a mixed pair splits into
    same-config ticks with both succeeding."""
    import time

    from tpufw.workloads.serve import _Server

    monkeypatch.setenv("TPUFW_BATCH_WAIT_MS", "300")
    srv = _Server(port=0, max_new_tokens=6)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    deadline = time.time() + 30
    while not hasattr(srv, "httpd") and time.time() < deadline:
        time.sleep(0.05)
    base = f"http://127.0.0.1:{srv.port}"

    def post(body):
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read())

    prompt = [[1, 5, 9]]
    greedy = post({"prompts": prompt, "max_new_tokens": 6})["outputs"]
    # Near-uniform sampling: matching all 6 greedy tokens has
    # probability ~V^-6 — and the server derives each tick's seed from
    # TPUFW_SEED + tick index, so given this fixed request order the
    # run is deterministic, not flaky.
    sampled = post({
        "prompts": prompt, "max_new_tokens": 6, "temperature": 100.0,
    })["outputs"]
    assert sampled != greedy
    # Ticks get distinct seeds: the SAME sampled request re-posted must
    # be able to differ (best-of-n would otherwise return n copies).
    # P(collision) ~ V^-6 per token under near-uniform sampling.
    sampled2 = post({
        "prompts": prompt, "max_new_tokens": 6, "temperature": 100.0,
    })["outputs"]
    assert sampled2 != sampled
    # Invalid values 400 with the field named, not garbage-200.
    # (urllib.error is loaded by urllib.request's module-level import.)
    with pytest.raises(urllib.error.HTTPError) as exc:
        post({
            "prompts": prompt, "max_new_tokens": 6, "temperature": -1.0,
        })
    assert exc.value.code == 400

    # Mixed concurrent trio: explicit-default must COALESCE with the
    # default request (the collapse-to-None branch — batched_with >= 2
    # for both), while the hot request splits into its own tick and
    # everyone succeeds with their exact expected outputs.
    results: dict[str, dict] = {}
    gate = threading.Barrier(3)

    def worker(name, body):
        gate.wait()  # post simultaneously: one coalescing window
        results[name] = post(body)

    threads = [
        threading.Thread(
            target=worker,
            args=("greedy", {"prompts": prompt, "max_new_tokens": 6}),
        ),
        threading.Thread(
            target=worker,
            args=(
                "explicit",
                {
                    "prompts": prompt,
                    "max_new_tokens": 6,
                    "temperature": 0.0,
                },
            ),
        ),
        threading.Thread(
            target=worker,
            args=(
                "hot",
                {
                    "prompts": prompt,
                    "max_new_tokens": 6,
                    "temperature": 100.0,
                },
            ),
        ),
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert results["greedy"]["outputs"] == greedy
    assert results["explicit"]["outputs"] == greedy
    # The hot request lands in a fresh tick (fresh seed), so only the
    # sampled-vs-greedy distinction is stable — not the exact tokens.
    assert results["hot"]["outputs"] != greedy
    assert results["greedy"]["batched_with"] >= 2
    assert results["explicit"]["batched_with"] >= 2
    srv.httpd.shutdown()


def test_http_server_batching_failure_isolation(tiny_env, monkeypatch):
    """Coalescing must not create shared fate: a request that fails (or
    only fails when co-batched, via the combined length bucket) falls
    back to per-request runs — innocent requests still get 200. And
    max_new_tokens < 1 is rejected up front (the pow2 tick bucket would
    otherwise bypass generate()'s own validation)."""
    import time

    from tpufw.workloads.serve import _Server

    monkeypatch.setenv("TPUFW_BATCH_WAIT_MS", "150")
    srv = _Server(port=0, max_new_tokens=4)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    deadline = time.time() + 30
    while not hasattr(srv, "httpd") and time.time() < deadline:
        time.sleep(0.05)
    base = f"http://127.0.0.1:{srv.port}"

    def post(body):
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=300) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    # max_new_tokens < 1: deterministic 400, never reaches the batcher.
    for bad_new in (0, -3):
        code, body = post(
            {"prompts": [[1, 2]], "max_new_tokens": bad_new}
        )
        assert code == 400 and "max_new_tokens" in body["error"]

    # Warm the single-request shape so the isolation fallback is fast.
    post({"prompts": [[1, 2, 3]], "max_new_tokens": 4})

    # tiny max_seq_len=128: a 140-token prompt fails alone AND in any
    # tick; the co-batched [1,2,3] must still succeed via fallback.
    results = {}

    def worker(name, prompts):
        results[name] = post({"prompts": prompts, "max_new_tokens": 4})

    threads = [
        threading.Thread(
            target=worker, args=("bad", [[1] * 140])
        ),
        threading.Thread(
            target=worker, args=("good", [[1, 2, 3]])
        ),
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert results["bad"][0] == 400, results["bad"]
    assert results["good"][0] == 200, results["good"]
    assert len(results["good"][1]["outputs"][0]) == 4
    srv.httpd.shutdown()


def test_eos_env_truncates_batch_outputs(monkeypatch, tmp_path):
    """TPUFW_EOS_ID flows into both serving modes: rows stop at the eos
    token (emitted, then truncated) instead of running to max_new."""
    from tpufw.workloads.serve import eos_from_env, run_batch

    monkeypatch.setenv("TPUFW_MODEL", "llama3_tiny")
    monkeypatch.setenv("TPUFW_CHECKPOINT_DIR", str(tmp_path / "none"))
    monkeypatch.delenv("TPUFW_EOS_ID", raising=False)
    assert eos_from_env() is None
    base = run_batch([[3, 1, 4]], max_new_tokens=6)[0]["output"]
    assert len(base) == 6
    # Greedy decode is deterministic: whatever token the model emits
    # first IS a reachable eos — set it and the row must stop there.
    monkeypatch.setenv("TPUFW_EOS_ID", str(base[0]))
    assert eos_from_env() == base[0]
    out = run_batch([[3, 1, 4]], max_new_tokens=6)[0]["output"]
    assert out == [base[0]]


def test_http_server_speculative_draft(tiny_env, monkeypatch):
    """TPUFW_DRAFT_MODEL composes with the slot scheduler (the default
    backend): the draft seeds the chunked verify path instead of
    rerouting all traffic through the legacy tick loop. Greedy outputs
    are EXACTLY the plain server's greedy outputs (the draft only
    changes speed), non-greedy sampling composes (the
    rejection-resample path), and TPUFW_SERVE_SLOTS=0 still opts back
    into the tick batcher."""
    import time

    from tpufw.workloads.serve import _Server, build_draft_generator

    srv = _Server(port=0, max_new_tokens=6)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    deadline = time.time() + 30
    while not hasattr(srv, "httpd") and time.time() < deadline:
        time.sleep(0.05)

    def post(port, prompts):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(
                {"prompts": prompts, "max_new_tokens": 6}
            ).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read())["outputs"]

    prompts = [[1, 5, 9], [2, 7]]
    want = post(srv.port, prompts)
    srv.httpd.shutdown()

    monkeypatch.setenv("TPUFW_DRAFT_MODEL", "llama3_tiny")
    srv2 = _Server(port=0, max_new_tokens=6)
    assert srv2._draft is not None
    # The dispatch fix: draft + default slots = the slot scheduler
    # with speculation wired in, NOT the legacy tick fallback.
    from tpufw.workloads.serve import _SlotScheduler

    assert isinstance(srv2._batcher, _SlotScheduler)
    assert srv2._batcher.spec_k == srv2._draft[2]
    t2 = threading.Thread(target=srv2.serve_forever, daemon=True)
    t2.start()
    deadline = time.time() + 30
    while not hasattr(srv2, "httpd") and time.time() < deadline:
        time.sleep(0.05)
    got = post(srv2.port, prompts)
    # Speculation observability: the accept-rate gauge and the
    # wasted-draft-FLOPs counter are exposed (a random-init draft
    # proposes junk, so the rate may be 0 — presence and the FLOPs
    # movement are the contract).
    with urllib.request.urlopen(
        f"http://127.0.0.1:{srv2.port}/metrics", timeout=30
    ) as resp:
        mtext = resp.read().decode()
    mvals = {
        ln.split()[0]: float(ln.split()[1])
        for ln in mtext.splitlines()
        if ln and not ln.startswith("#")
    }
    assert "tpufw_spec_accept_rate" in mvals
    assert "tpufw_spec_fallback_slots" in mvals
    assert mvals["tpufw_spec_wasted_draft_flops_total"] >= 0.0
    assert mvals["tpufw_serve_ticks_total"] >= 1
    srv2.httpd.shutdown()
    assert got == want

    # Explicit TPUFW_SERVE_SLOTS=0 restores the legacy speculative
    # tick batcher (construction-only: dispatch is decided in
    # __init__, no request needed).
    monkeypatch.setenv("TPUFW_SERVE_SLOTS", "0")
    monkeypatch.setenv("TPUFW_WARMUP", "0")
    srv_tick = _Server(port=0, max_new_tokens=6)
    assert not isinstance(srv_tick._batcher, _SlotScheduler)
    monkeypatch.delenv("TPUFW_SERVE_SLOTS")
    monkeypatch.setenv("TPUFW_WARMUP", "1")

    # Non-greedy + draft now composes (stochastic speculative
    # sampling): a server with TPUFW_TEMPERATURE=0.7 and a draft must
    # serve a real request end-to-end (the jit path with a non-greedy
    # SamplingConfig static arg), not just resolve config.
    monkeypatch.setenv("TPUFW_TEMPERATURE", "0.7")
    from tpufw.workloads.serve import sampling_from_env

    assert build_draft_generator(sampling_from_env()) is not None
    srv3 = _Server(port=0, max_new_tokens=6)
    t3 = threading.Thread(target=srv3.serve_forever, daemon=True)
    t3.start()
    deadline = time.time() + 30
    while not hasattr(srv3, "httpd") and time.time() < deadline:
        time.sleep(0.05)
    sampled = post(srv3.port, prompts)
    # Per-request repetition_penalty composes with the draft end-to-end
    # (the penalized speculative jit path, not just config resolution)
    # — this used to 400.
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv3.port}/generate",
        data=json.dumps({
            "prompts": prompts,
            "max_new_tokens": 6,
            "repetition_penalty": 1.3,
        }).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=300) as resp:
        penalized = json.loads(resp.read())["outputs"]
    srv3.httpd.shutdown()
    assert len(sampled) == len(prompts)
    assert all(len(o) == 6 for o in sampled)
    assert len(penalized) == len(prompts)
    assert all(len(o) == 6 for o in penalized)


@pytest.mark.parametrize("backend", ["slots", "tick"])
def test_warmup_invisible_to_metrics_and_seed_replay(
    tiny_env, monkeypatch, backend
):
    """_Server warmup (default on) pre-compiles the serving path but
    must be invisible: rng-stream indices back at 0 (seed replay
    unchanged) and no counter movement — the warmup runs before the
    listener binds, so nothing can observe the interim state. A spy
    proves the warmup actually RAN (it swallows exceptions and
    TPUFW_WARMUP=0 skips it, either of which would make the
    post-state assertions vacuously true). Both scheduler backends:
    the slot scheduler (default) and the legacy tick batcher."""
    from tpufw.workloads import serve as serve_mod

    calls = []
    if backend == "tick":
        monkeypatch.setenv("TPUFW_SERVE_SLOTS", "0")
        real_tick = serve_mod._Server._run_tick

        def tick_spy(self, prompts, max_new, sampling):
            calls.append((len(prompts), max_new))
            return real_tick(self, prompts, max_new, sampling)

        monkeypatch.setattr(serve_mod._Server, "_run_tick", tick_spy)
    else:
        real_admit = serve_mod._SlotScheduler._admit_job

        def admit_spy(self, req, job, slot):
            calls.append(slot)
            return real_admit(self, req, job, slot)

        monkeypatch.setattr(
            serve_mod._SlotScheduler, "_admit_job", admit_spy
        )
    srv = serve_mod._Server(port=0, max_new_tokens=4)
    assert calls, "warmup never ran"
    if backend == "tick":
        assert isinstance(srv._batcher, serve_mod._Batcher)
        assert srv._tick_index == 0
    else:
        assert isinstance(srv._batcher, serve_mod._SlotScheduler)
        assert srv._batcher._job_index == 0
        assert srv._batcher._chunk_index == 0
        assert srv._batcher._keys_ahead is None
    rendered = srv.metrics.render({})
    if backend == "slots":
        assert "\ntpufw_serve_chunks_chained_total 0\n" in rendered
    for line in rendered.splitlines():
        if line.startswith("tpufw_serve_") and not line.startswith("#"):
            assert line.endswith(" 0"), line


def test_server_with_a_telemetry_dir_writes_the_request_chain(
    tiny_env, monkeypatch, tmp_path
):
    """``TPUFW_TELEMETRY_DIR`` set: the server starts (it did not, from
    PR 21 to PR 38: its run info read ``jax`` where nothing had imported
    it), the scheduler's spans go to ``trace-serve.json``, and one
    request's three legs there, ``req_queue``, ``req_prefill`` and
    ``req_decode``, carry the ``rid`` of its ``serve_request`` event."""
    import json

    from tpufw.obs import events as events_mod
    from tpufw.workloads import serve as serve_mod

    tel = tmp_path / "tel"
    monkeypatch.setenv("TPUFW_TELEMETRY_DIR", str(tel))
    monkeypatch.setenv("TPUFW_SERVE_PAGE", "16")
    monkeypatch.setenv("TPUFW_SERVE_PREFILL_CHUNK", "1")
    srv = serve_mod._Server(port=0, max_new_tokens=4)
    out, _ = srv._batcher.submit([[3, 5, 9, 2, 6] * 4], 12, None)
    assert len(out[0]) == 12
    srv._tel.close()
    doc = json.loads((tel / "trace-serve.json").read_text())
    legs = {}
    for e in doc["traceEvents"]:
        if e["name"] in ("req_queue", "req_prefill", "req_decode"):
            legs.setdefault(e["args"]["rid"], {})[e["name"]] = e["args"]
    # rid 1 was warm-up's request, rid 2 is this one.
    assert set(legs[2]) == {"req_queue", "req_prefill", "req_decode"}
    assert legs[2]["req_decode"]["tokens"] == 12
    assert legs[2]["req_decode"]["passes"] >= 1
    assert legs[2]["req_prefill"]["chunks"] == 2
    waits = [
        e["args"]["for"] for e in doc["traceEvents"]
        if e["name"] == "serve_device_wait"
    ]
    assert {"decode", "prefill_final"} <= set(waits)
    done = [
        e for e in events_mod.read_events(str(tel / "events.jsonl"))
        if e["kind"] == "serve_request"
    ]
    assert 2 in {e["rid"] for e in done}


def test_paged_server_traces_its_row_model_once(tiny_env, monkeypatch):
    """The paged server's warm-up builds the pool that serves, and
    with it the row twin's shapes: ``row_shape_traces_total`` reads
    that one trace on a warm server (it is NOT reset with the traffic
    counters, whose warm-up stays invisible) and chunked admissions
    after it add none, nor a pool switch."""
    from tpufw.workloads import serve as serve_mod

    monkeypatch.setenv("TPUFW_SERVE_PAGE", "16")
    monkeypatch.setenv("TPUFW_SERVE_PREFILL_CHUNK", "1")
    srv = serve_mod._Server(port=0, max_new_tokens=4)

    def reading(name):
        text = srv.metrics.render({})
        (line,) = [
            ln for ln in text.splitlines() if ln.split(" ")[0] == name
        ]
        return float(line.split(" ")[1])

    assert reading("tpufw_serve_row_shape_traces_total") == 1
    assert reading("tpufw_serve_pool_switches_total") == 0
    for first in (3, 4, 5):
        srv._batcher.submit([[first, 5, 9, 2, 6] * 4], 4, None)
    assert reading("tpufw_serve_retired_rows_total") == 3
    assert reading("tpufw_serve_row_shape_traces_total") == 1
    assert reading("tpufw_serve_pool_switches_total") == 0


# ---- _Batcher._take_tick policy (no server, no device work) ----


def _bare_batcher(max_rows=64):
    """A _Batcher with no worker thread: _take_tick is pure queue
    policy, so it is testable directly against a hand-built queue."""
    from tpufw.workloads.serve import _Batcher

    b = _Batcher.__new__(_Batcher)
    b._queue = []
    b._cv = threading.Condition()
    b.max_rows = max_rows
    b.wait_s = 0.0
    b._metrics = None
    return b


def _pending(n_rows=1, sampling=None, stream=False):
    from tpufw.workloads.serve import _Pending

    return _Pending(
        [[1]] * n_rows, 4, sampling,
        stream_q=object() if stream else None,
    )


def test_take_tick_coalesces_compatible_requests():
    b = _bare_batcher()
    pends = [_pending(), _pending(2), _pending()]
    b._queue = list(pends)
    assert b._take_tick() == pends
    assert b._queue == []


def test_take_tick_budget_closes_fifo():
    """Once a same-config request misses the row budget, no later
    same-config request may overtake it into the tick — even one
    small enough to fit."""
    b = _bare_batcher(max_rows=3)
    a, big, small = _pending(2), _pending(2), _pending(1)
    b._queue = [a, big, small]
    assert b._take_tick() == [a]
    assert b._queue == [big, small]
    assert b._take_tick() == [big, small]


def test_take_tick_diverts_sampling_mismatch_keeping_order():
    from tpufw.infer import SamplingConfig

    hot = SamplingConfig(temperature=1.0)
    b = _bare_batcher()
    a, m, c = _pending(), _pending(sampling=hot), _pending()
    b._queue = [a, m, c]
    assert b._take_tick() == [a, c]
    assert b._queue == [m]
    assert b._take_tick() == [m]  # mismatch heads the next tick


def test_take_tick_stream_runs_solo():
    b = _bare_batcher()
    s, a = _pending(stream=True), _pending()
    b._queue = [s, a]
    assert b._take_tick() == [s]  # stream head: solo tick
    assert b._queue == [a]
    b2 = _bare_batcher()
    x, s2, y = _pending(), _pending(stream=True), _pending()
    b2._queue = [x, s2, y]
    assert b2._take_tick() == [x, y]  # stream never joins a batch
    assert b2._queue == [s2]
