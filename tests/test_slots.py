"""Slot-pool continuous batching (tpufw.infer.slots + _SlotScheduler).

Three contracts, all on CPU with the tiny model:

- PARITY: a row decoded through the slot pool (insert -> chunked
  decode_steps -> retire) emits exactly the one-shot ``generate``
  path's greedy tokens — chunk partitioning and co-resident rows
  must be invisible to the math (same per-step carry).
- SHAPE STABILITY: occupancy is data, not shape. After the first
  chunk ladder is traced, insert/retire churn and new requests add
  ZERO jit traces (``slots_mod.TRACE_COUNTS`` is bumped inside the
  jitted bodies, so it counts traces, not calls).
- SCHEDULING: rows join and leave MID-FLIGHT — a short request
  submitted while a long one is decoding completes first, and a
  streaming request shares decode chunks with a non-streamed one
  instead of serializing it.
"""

import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.infer import SamplingConfig, generate_text
from tpufw.infer import slots as slots_mod
from tpufw.models import LLAMA_CONFIGS, Llama

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_decode():
    cfg = LLAMA_CONFIGS["llama3_tiny"].decode_config()
    model = Llama(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def test_pool_matches_generate_and_is_shape_stable(tiny_decode):
    model, params = tiny_decode
    prompts = [[1, 5, 9], [2, 7], [3]]
    max_new = 6
    want = generate_text(
        model, params, prompts, max_new_tokens=max_new, sampling=GREEDY
    )

    pool = slots_mod.SlotPool.create(
        model, params, 4, sampling=GREEDY, eos_id=None
    )
    rows: dict[int, list] = {}
    for i, p in enumerate(prompts):
        rng = jax.random.fold_in(jax.random.key(0), i)
        cache, _first, first_int, _done, seen = slots_mod.prefill_row(
            model, params, p, rng, sampling=GREEDY, eos_id=None, pad_to=64
        )
        pool.insert(i, cache, first_int, len(p), max_new - 1, row_seen=seen)
        rows[i] = [first_int]
    chunk_i = 0
    while any(len(t) < max_new for t in rows.values()):
        key = jax.random.fold_in(jax.random.key(1), chunk_i)
        chunk_i += 1
        out = np.asarray(pool.decode_steps(jax.random.split(key, 2)))
        for i in rows:
            take = min(2, max_new - len(rows[i]))
            rows[i].extend(out[i, :take].tolist())
    assert [rows[i] for i in range(len(prompts))] == want

    # Steady state reached: retire a row, insert a fresh one into a
    # DIFFERENT slot, decode again — zero new traces (the slot index
    # is traced data; shapes never change).
    before = dict(slots_mod.TRACE_COUNTS)
    pool.retire(1)
    rng = jax.random.fold_in(jax.random.key(0), 99)
    cache, _first, first_int, _done, seen = slots_mod.prefill_row(
        model, params, [4, 4], rng, sampling=GREEDY, eos_id=None, pad_to=64
    )
    pool.insert(3, cache, first_int, 2, max_new - 1, row_seen=seen)
    out = np.asarray(pool.decode_steps(jax.random.split(jax.random.key(7), 2)))
    solo = generate_text(
        model, params, [[4, 4]], max_new_tokens=3, sampling=GREEDY
    )[0]
    assert [first_int] + out[3].tolist() == solo
    after = dict(slots_mod.TRACE_COUNTS)
    assert after["insert"] == before["insert"]
    assert after["decode_steps"] == before["decode_steps"]


def _make_scheduler(model, params):
    from tpufw.workloads.serve import _SlotScheduler

    return _SlotScheduler(
        model, params, eos_id=None, default_sampling=GREEDY, seed_base=0
    )


def test_scheduler_mid_flight_join_and_leave(tiny_decode, monkeypatch):
    """A short request submitted while a long one is decoding joins a
    free slot at a chunk boundary and COMPLETES while the long one is
    still running — the defining behavior the tick batcher could not
    produce. Outputs stay bit-equal to the one-shot generate path,
    and once the chunk ladder is traced, further requests add zero
    traces."""
    monkeypatch.setenv("TPUFW_SERVE_CHUNK", "2")
    model, params = tiny_decode
    sched = _make_scheduler(model, params)
    long_new, short_new = 24, 4
    done: dict = {}

    def run(name, prompt, max_new):
        outs, bw = sched.submit([prompt], max_new, None)
        done[name] = (time.monotonic(), outs, bw)

    long_t = threading.Thread(target=run, args=("long", [1, 2, 3], long_new))
    long_t.start()
    deadline = time.monotonic() + 120
    while sched.slots_occupied == 0 and time.monotonic() < deadline:
        time.sleep(0.002)
    assert sched.slots_occupied, "long request never occupied a slot"
    short_t = threading.Thread(target=run, args=("short", [4, 5], short_new))
    short_t.start()
    long_t.join(timeout=300)
    short_t.join(timeout=300)
    t_long, long_out, long_bw = done["long"]
    t_short, short_out, short_bw = done["short"]
    assert len(long_out[0]) == long_new
    assert len(short_out[0]) == short_new
    # The short row retired mid-flight; the long one kept decoding.
    assert t_short < t_long
    # Both saw the other in the pool.
    assert long_bw >= 2 and short_bw >= 2
    # Greedy parity with the one-shot path: joins, leaves, and chunk
    # partitioning are invisible to the per-step math.
    assert long_out == generate_text(
        model, params, [[1, 2, 3]], max_new_tokens=long_new, sampling=GREEDY
    )
    assert short_out == generate_text(
        model, params, [[4, 5]], max_new_tokens=short_new, sampling=GREEDY
    )

    # Steady state: another request through the warm scheduler — same
    # prompt bucket, same chunk ladder — must trace NOTHING new.
    before = dict(slots_mod.TRACE_COUNTS)
    outs, _ = sched.submit([[9, 8, 7]], short_new, None)
    assert len(outs[0]) == short_new
    after = dict(slots_mod.TRACE_COUNTS)
    assert after["insert"] == before["insert"]
    assert after["decode_steps"] == before["decode_steps"]


def test_scheduler_stream_shares_chunks(tiny_decode, monkeypatch):
    """A streaming request is an ordinary slot occupant: it decodes
    in the same chunks as a concurrent non-streamed request (the tick
    batcher ran streams as SOLO ticks), flushing at most chunk-size
    tokens per row per event, and its concatenation equals the
    one-shot greedy output."""
    monkeypatch.setenv("TPUFW_SERVE_CHUNK", "2")
    model, params = tiny_decode
    sched = _make_scheduler(model, params)
    stream_new = 8
    done: dict = {}

    def run(name, prompt, max_new):
        outs, bw = sched.submit([prompt], max_new, None)
        done[name] = (outs, bw)

    long_t = threading.Thread(target=run, args=("long", [1, 2, 3], 24))
    long_t.start()
    deadline = time.monotonic() + 120
    while sched.slots_occupied == 0 and time.monotonic() < deadline:
        time.sleep(0.002)
    q: queue.Queue = queue.Queue()
    sched.submit_stream([[6, 7]], stream_new, None, q)
    events = []
    while True:
        kind, payload = q.get(timeout=120)
        events.append((kind, payload))
        if kind in ("done", "error"):
            break
    long_t.join(timeout=300)
    assert events[-1][0] == "done", events[-1]
    chunks = [rows for kind, rows in events[:-1] if kind == "chunk"]
    assert len(chunks) >= 2  # it actually streamed
    # Every flush carries at most chunk-size tokens per row (the
    # admission flush carries exactly the prefill token).
    assert all(len(rows[0]) <= 2 for rows in chunks)
    got = [t for rows in chunks for t in rows[0]]
    assert got == generate_text(
        model, params, [[6, 7]], max_new_tokens=stream_new, sampling=GREEDY
    )[0]
    # The non-streamed request shared the pool with the stream.
    assert done["long"][1] >= 2


# ------------------------------------------- the pass's chained order
#
# ``_SlotScheduler._run_chunk``: where a decode chunk's boundary is quiet
# (nothing queued, nobody prefilling, a row with budget left) the
# successor is enqueued BEFORE the chunk is read. The double below sees
# every pool call and every emit in the order the scheduler's thread made
# them, and queues the arrivals itself from inside that thread, at a
# call of its choosing: no test here depends on a race.

SAMPLED = SamplingConfig(temperature=0.9, top_k=50)
SEED = 3
POOLS = {
    "contiguous": dict(page=0),
    "paged": dict(page=16, prefill_chunk_pages=0, prefix_cache=False),
    "chunked": dict(page=16, prefill_chunk_pages=1, prefix_cache=False),
}


class _Recorder:
    """A scheduler whose pool calls and emits are on one record, in
    order: ("decode", k), ("emit",), ("prefill", what), ("release",
    slot). ``on_decode[n]`` runs inside the n-th ``decode_steps`` call
    (1-based), on the scheduler's thread, before the program is
    enqueued. ``plain`` holds the scheduler to the plain order (the
    parent's: every boundary taken as not quiet)."""

    def __init__(self, model, params, pool, monkeypatch, *, chunk=4,
                 sampling=GREEDY, plain=False, **kw):
        from tpufw.infer import pages as pages_mod
        from tpufw.workloads.serve import _Metrics, _SlotScheduler

        monkeypatch.setenv("TPUFW_SERVE_CHUNK", str(chunk))
        self.record, self.keys, self.pages_at_launch = [], [], []
        self.on_decode: dict = {}
        self.metrics = _Metrics()
        rec = self

        def wrap(cls, name, entry):
            real = getattr(cls, name)

            def double(pool_self, *a, **k):
                if getattr(pool_self, "_rec", None) is rec:
                    entry(pool_self, *a, **k)
                return real(pool_self, *a, **k)

            monkeypatch.setattr(cls, name, double)

        def decode(pool_self, keys):
            self.record.append(("decode", len(keys)))
            self.keys.append(np.asarray(jax.random.key_data(keys)))
            self.pages_at_launch.append(
                {s: list(p) for s, p in
                 enumerate(getattr(pool_self, "slot_pages", []))}
            )
            hook = self.on_decode.pop(
                sum(r[0] == "decode" for r in self.record), None
            )
            if hook is not None:
                hook()

        wrap(slots_mod.SlotPool, "decode_steps", decode)
        wrap(slots_mod.SlotPool, "insert",
             lambda p, slot, *a, **k: self.record.append(("prefill", "insert")))
        wrap(pages_mod.PagedSlotPool, "insert_paged",
             lambda p, slot, *a, **k: self.record.append(("prefill", "insert")))
        wrap(pages_mod.PagedSlotPool, "chunk_step",
             lambda p, cp, *a, **k: self.record.append(("prefill", "chunk")))
        wrap(pages_mod.PagedSlotPool, "release_slot",
             lambda p, slot: self.record.append(("release", slot)))
        self.sched = _SlotScheduler(
            model, params, eos_id=None, default_sampling=sampling,
            seed_base=SEED, metrics=self.metrics, **POOLS[pool], **kw,
        )
        if plain:
            self.sched._boundary_is_quiet = lambda: False
        build, emit = self.sched._build_pool, self.sched._emit_chunk

        def build_marked(key):
            build(key)
            self.sched._pool._rec = rec

        def emit_recorded(*a):
            self.record.append(("emit",))
            return emit(*a)

        self.sched._build_pool = build_marked
        self.sched._emit_chunk = emit_recorded

    def counter(self, name):
        return self.metrics.registry.counter("tpufw_serve_" + name).value()

    def stream(self, prompt, max_new):
        q = queue.Queue()
        self.sched.submit_stream([prompt], max_new, None, q)
        return q

    def kinds(self, start=0, stop=None):
        """The record's entries by kind, as one string to search."""
        return " ".join(r[0] for r in self.record[start:stop])


def _drain(q):
    got = []
    while True:
        kind, payload = q.get(timeout=300)
        if kind == "chunk":
            got.extend(payload[0])
        elif kind == "done":
            return got
        else:
            raise payload


@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_chained_chunks_serve_the_plain_orders_tokens(
    tiny_decode, monkeypatch, pool, sampling
):
    """The same requests through the chained order and through the plain
    one (the parent's) give the same tokens, token for token, greedy and
    sampled at a fixed seed; every chunk but a request's first is
    chained; and each chunk, chained or not, takes the step keys of its
    own chunk index, the ones the parent made for it."""
    model, params = tiny_decode
    prompts = [[1, 5, 9, 2], [2, 7], [3, 3, 3, 8, 1]]
    outs = {}
    for plain in (True, False):
        rec = _Recorder(
            model, params, pool, monkeypatch, sampling=sampling, plain=plain
        )
        # 20 steps = five chunks of 4; then 9 steps = 4, 4 and 1.
        outs[plain] = (
            rec.sched.submit(prompts, 21, None)[0],
            rec.sched.submit([[4, 4]], 10, None)[0],
        )
        assert [k for what, *k in rec.record if what == "decode"] == [
            [4], [4], [4], [4], [4], [4], [4], [1]
        ]
        assert rec.counter("ticks_total") == 8
        assert rec.counter("chunks_chained_total") == (0 if plain else 6)
        for i, got in enumerate(rec.keys):
            want = jax.random.split(
                jax.random.fold_in(jax.random.key(SEED + 1), i), len(got)
            )
            assert np.array_equal(got, jax.random.key_data(want)), i
    assert outs[False] == outs[True]
    if sampling is GREEDY:
        assert outs[False][0] == generate_text(
            model, params, prompts, max_new_tokens=21, sampling=GREEDY
        )
    # In the chained order (the loop's last) a successor goes out before
    # its predecessor's emit.
    assert "decode decode emit" in rec.kinds()


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_a_row_that_ends_with_its_successor_in_flight(
    tiny_decode, monkeypatch, pool
):
    """B (6 tokens) joins A (41) and ends one step into a chunk whose
    successor is already enqueued. It is handed nothing of the
    successor, its slot's next occupant C decodes what C decodes alone,
    its pages are freed once, behind the successor and ahead of C's
    admission, and its export reads the pages it held at its own chunk's
    launch: below its last cursor they are, byte for byte, what the
    plain order exports."""
    model, params = tiny_decode
    a, b, c = [1, 2, 3], [4, 5] * 9, [6, 7, 8, 9]
    want = {
        tuple(p): generate_text(
            model, params, [p], max_new_tokens=n, sampling=GREEDY
        )[0]
        for p, n in ((a, 41), (b, 6), (c, 9))
    }
    exports = {}
    held = lambda p, n: -(-(len(p) + n - 1) // 16)  # pages of a row
    for plain in (True, False):
        export = {}
        rec = _Recorder(
            model, params, pool, monkeypatch, plain=plain,
            **(dict(page_export=lambda job, state: export.update(
                {tuple(job.prompt): state})) if pool != "contiguous" else {}),
        )
        sched, queues, retired = rec.sched, {}, []
        rec.on_decode[2] = lambda: queues.update(b=rec.stream(b, 6))
        retire = sched._retire_slot

        def retire_recorded(slot, *, device):
            job = sched._slots[slot]
            retired.append((
                tuple(job.prompt), slot, sched._inflight is not None,
                sum(r[0] == "decode" for r in rec.record),
            ))
            retire(slot, device=device)
            if job.prompt == b:  # C arrives once B's slot is free
                queues.update(c=rec.stream(c, 9))

        sched._retire_slot = retire_recorded
        assert sched.submit([a], 41, None)[0] == [want[tuple(a)]]
        assert _drain(queues["b"]) == want[tuple(b)]
        assert _drain(queues["c"]) == want[tuple(c)]
        by_prompt = {r[0]: r for r in retired}
        _, b_slot, in_flight, launched = by_prompt[tuple(b)]
        assert by_prompt[tuple(c)][1] == b_slot  # C took B's slot
        # B ended with the successor enqueued, in the chained order only.
        assert in_flight == (not plain)
        if pool != "contiguous":
            alloc = sched._pool.allocator
            assert alloc.in_use == 0 and len(set(alloc.free)) == len(alloc.free)
            assert rec.counter("pages_freed_total") == (
                held(a, 41) + held(b, 6) + held(c, 9)
            )
            # The release is enqueued behind the successor (one more
            # chunk launched than emitted, in the chained order) and
            # ahead of C's first prefill call.
            i = rec.record.index(("release", b_slot))
            emitted = sum(r[0] == "emit" for r in rec.record[:i])
            assert launched == emitted + (not plain)
            assert sum(r[0] == "decode" for r in rec.record[:i]) == launched
            nxt = [r for r in rec.record[i:] if r[0] in ("prefill", "decode")]
            assert nxt[0][0] == "prefill"
            # The export read the pages of B's own chunk's launch.
            own = launched - 1 - (not plain)
            assert export[tuple(b)]["n_pages"] == len(
                rec.pages_at_launch[own][b_slot]
            ) == held(b, 6)
            exports[plain] = export[tuple(b)]
    if exports:
        last = len(b) + 6 - 1  # B's cursor once its last token is out
        for got, ref in zip(exports[False]["arrays"], exports[True]["arrays"]):
            flat = lambda x: x.reshape(x.shape[0], -1, *x.shape[3:])[:, :last]
            assert flat(got).tobytes() == flat(ref).tobytes()


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_an_arrival_or_a_prefilling_slot_holds_the_plain_order(
    tiny_decode, monkeypatch, pool
):
    """B is queued from inside the enqueue of A's third chunk, itself a
    chained successor. At that chunk's boundary no successor goes out:
    the pool sees the plain order's calls, and B's first prefill call is
    enqueued behind exactly the chunk it is behind in the plain order
    (the third). While B prefills (three chunks in the chunked pool)
    every decode chunk is read and emitted before the next goes out;
    once B decodes, chunks chain again."""
    model, params = tiny_decode
    a, b = [1, 2, 3], [4, 5] * 20  # B: 40 tokens, three pages
    records = {}
    for plain in (True, False):
        rec = _Recorder(model, params, pool, monkeypatch, plain=plain)
        queues = {}
        rec.on_decode[3] = lambda: queues.update(b=rec.stream(b, 13))
        out = rec.sched.submit([a], 41, None)[0]
        assert out == generate_text(
            model, params, [a], max_new_tokens=41, sampling=GREEDY
        )
        assert _drain(queues["b"]) == generate_text(
            model, params, [b], max_new_tokens=13, sampling=GREEDY
        )[0]
        records[plain] = [r for r in rec.record if r[0] != "release"]
    # B's first prefill call: the first one once A decodes.
    first = {
        p: next(
            i for i, x in enumerate(r)
            if x[0] == "prefill" and ("decode", 4) in r[:i]
        )
        for p, r in records.items()
    }
    for plain, record in records.items():
        at = first[plain]
        # Behind the third chunk, which was read and emitted first.
        assert sum(r[0] == "decode" for r in record[:at]) == 3
        assert record[at - 1] == ("emit",) and record[at + 1][0] != "emit"
    # From the arrival to B's last prefill call, the chained scheduler's
    # pool saw the plain order's calls, one for one.
    last = {
        p: max(i for i, x in enumerate(r) if x[0] == "prefill")
        for p, r in records.items()
    }
    chained, plain = records[False], records[True]
    assert chained[first[False] - 1:last[False] + 2] == plain[
        first[True] - 1:last[True] + 2
    ]
    assert sum(r[0] == "prefill" for r in plain[first[True]:]) == (
        4 if pool == "chunked" else 1  # three chunks and the insert
    )
    # Before the arrival and after B's prefill, chunks chain.
    kinds = lambda record: " ".join(r[0] for r in record)
    assert "decode decode emit" in kinds(chained[:first[False]])
    assert "decode decode emit" in kinds(chained[last[False]:])
    assert "decode decode" not in kinds(plain)


def test_resets_from_another_thread_never_hand_a_chunk_anothers_keys(
    tiny_decode, monkeypatch
):
    """``reset_after_warmup`` takes the chunk index back to 0 from the
    caller's thread while the scheduler's thread makes keys ahead and
    takes them: whatever the interleaving, every chunk is enqueued with
    the step keys of SOME chunk index at its own length (keys made ahead
    are named by index and length, and taken only under that name), the
    scheduler lives, and greedy tokens are what they are."""
    import sys

    model, params = tiny_decode
    rec = _Recorder(model, params, "contiguous", monkeypatch, chunk=2)
    want = generate_text(
        model, params, [[1, 2, 3]], max_new_tokens=33, sampling=GREEDY
    )
    assert rec.sched.submit([[1, 2, 3]], 33, None)[0] == want  # compiled
    stop = threading.Event()

    def resets():
        while not stop.is_set():
            rec.sched.reset_after_warmup()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=resets) for _ in range(3)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            assert rec.sched.submit([[1, 2, 3]], 33, None)[0] == want
        stop.set()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert rec.sched._thread.is_alive() and len(rec.keys) > 32
    valid = {
        np.asarray(jax.random.key_data(jax.random.split(
            jax.random.fold_in(jax.random.key(SEED + 1), i), 2
        ))).tobytes()
        for i in range(17)  # a request runs 16 chunks from the last reset
    }
    assert {k.tobytes() for k in rec.keys} <= valid
