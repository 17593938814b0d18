"""The slot scheduler's ledger of passes (``serve._PassLedger``) on a
clock the test sets: every identity is exact, none is held to the wall
clock of a loaded machine. The spans are nested as ``_SlotScheduler``
nests them and the pools' ``dispatched`` hook is called where a program's
call returns; times are sums of powers of two, so floats add exactly.

The real scheduler's side (one kind a pass on a toy server, the steps
against the chunks, ``req_decode``) is in tests/test_chunked_prefill.py;
the benchmark's four readers in tests/bench/test_pass_ledger.py."""

import pytest

from tpufw.obs import trace as trace_mod
from tpufw.obs.registry import Registry
from tpufw.workloads import serve as serve_mod

KINDS = serve_mod.PASS_KINDS


class _HandClock:
    """A clock the test sets: every read returns ``now``."""

    def __init__(self):
        self.now = 64.0

    def __call__(self):
        return self.now


class _Sched:
    """The scheduler thread's pass, as far as the ledger sees it."""

    def __init__(self):
        self.clock = _HandClock()
        self.tracer = trace_mod.Tracer(None, clock=self.clock)
        self.reg = Registry()
        self.phase_s = self.reg.counter("tpufw_serve_phase_seconds_total")
        self.tracer.listeners.append(
            lambda name, dur, args, self_s: self.phase_s.inc(self_s, phase=name)
        )
        self.ledger = serve_mod._PassLedger(self.tracer, self.reg, clock=self.clock)

    def tick(self, s):
        self.clock.now += s

    def wait(self, s):
        with self.tracer.span("serve_wait"):
            self.tick(s)

    def admit(self, s):
        with self.tracer.span("serve_admit", queued=1):
            self.tick(s)

    def chunk(self, before, after, row=None, final=None):
        """One prefill chunk: ``before`` s of host work, the dispatch's
        return, ``after`` s more; ``row`` = (before, after) of the row's
        zero-fill inside it; ``final`` = (wait, fetch) of the blocking
        read."""
        with self.tracer.span("serve_prefill_chunk", final=bool(final)):
            if row:
                with self.tracer.span("serve_row_alloc"):
                    self.tick(row[0])
                    self.ledger.fed("row")
                    self.tick(row[1])
            self.tick(before)
            self.ledger.fed("chunk")
            self.tick(after)
            if final:
                with self.tracer.span("serve_device_wait", **{"for": "prefill_final"}):
                    self.tick(final[0])
                with self.tracer.span("serve_fetch"):
                    self.tick(final[1])

    def emit(self, before, insert_after=None):
        with self.tracer.span("serve_emit"):
            self.tick(before)
            if insert_after is not None:
                self.ledger.fed("insert")
                self.tick(insert_after)

    def decode(self, k, dispatch, wait, fetch, name="serve_decode_chunk", successor=None):
        """One decode chunk as its pass sees it. ``dispatch=None``: the
        pass found the chunk in flight and enqueues none of its own.
        ``successor`` = (plan, glue, call): the chained order, the next
        chunk planned and its keys made before the wait (``plan`` s, the
        device running), then, once the wait returns, ``glue`` s of the
        loop's own lines and ``call`` s up to the return of the
        successor's dispatch, ahead of the fetch."""
        with self.tracer.span(name, k=k, rows=1, ahead=self.ledger.ahead):
            if dispatch is not None:
                with self.tracer.span("serve_decode_dispatch"):
                    self.tick(dispatch)
                    self.ledger.fed("decode")
            if successor:
                self.tick(successor[0])
            with self.tracer.span("serve_device_wait", **{"for": "decode"}):
                self.tick(wait)
            if successor:
                self.tick(successor[1])
                with self.tracer.span("serve_decode_dispatch"):
                    self.tick(successor[2])
                    self.ledger.fed("decode")
            with self.tracer.span("serve_fetch"):
                self.tick(fetch)

    def seconds(self, kind):
        return self.reg.counter("tpufw_serve_pass_seconds_total").value(**{"pass": kind})

    def steps(self, kind):
        return self.reg.counter("tpufw_serve_pass_steps_total").value(**{"pass": kind})

    def starved(self, kind, phase=None):
        c = self.reg.counter("tpufw_serve_pass_starved_seconds_total")
        phases = [phase] if phase else serve_mod.SCHED_PHASES
        return sum(c.value(phase=p, **{"pass": kind}) for p in phases)


def _three_passes(s):
    """An admission of two chunks beside nothing, then a lone decode: a
    ``prefill_only`` pass, a ``decode_behind_prefill`` pass, a ``decode``
    pass, a wait on either side. The device is fed from start-up until
    the first read."""
    s.wait(8.0)
    # Pass 1, prefill_only (fed throughout: nothing was read yet).
    s.admit(0.5)
    s.chunk(0.25, 0.125, row=(0.0625, 0.03125))
    s.emit(0.015625)
    s.ledger.end_pass()
    # Pass 2, decode_behind_prefill: the final chunk, its insert, the chunk.
    s.tick(0.0078125)  # the loop's own lines, under no span
    s.chunk(0.25, 0.125, final=(2.0, 0.5))  # drained once the wait ends
    s.emit(0.25, insert_after=0.0625)  # starved up to the insert's return
    s.decode(8, 0.5, 4.0, 0.25)  # fed on entry: only the fetch is starved
    s.emit(1.0)
    s.ledger.end_pass()
    # Pass 3, decode: starved from the fetch before it to its dispatch.
    s.tick(0.0078125)
    s.admit(0.125)
    s.decode(16, 0.5, 8.0, 0.25)
    s.emit(1.0)
    s.ledger.end_pass()
    s.wait(16.0)


def test_three_passes_by_hand():
    s = _Sched()
    t0 = s.clock.now
    _three_passes(s)
    assert s.seconds("prefill_only") == 0.5 + 0.0625 + 0.03125 + 0.25 + 0.125 + 0.015625
    assert s.seconds("decode_behind_prefill") == (
        0.0078125 + 0.25 + 0.125 + 2.0 + 0.5 + 0.25 + 0.0625 + 0.5 + 4.0 + 0.25 + 1.0
    )
    assert s.seconds("decode") == 0.0078125 + 0.125 + 0.5 + 8.0 + 0.25 + 1.0
    # Passes and serve_wait tile the thread's time.
    assert sum(s.seconds(k) for k in KINDS) + s.phase_s.value(phase="serve_wait") == s.clock.now - t0
    assert (s.steps("prefill_only"), s.steps("decode_behind_prefill"), s.steps("decode")) == (0, 8, 16)
    # Fed from start-up until the first read: nothing starved in pass 1.
    assert s.starved("prefill_only") == 0.0
    # Pass 2. A phase that straddles a dispatch is split at its return:
    # of the emit that inserted, the 0.25 s before the insert.
    assert s.starved("decode_behind_prefill", "serve_fetch") == 0.5 + 0.25
    assert s.starved("decode_behind_prefill", "serve_emit") == 0.25 + 1.0
    assert s.starved("decode_behind_prefill") == 0.5 + 0.25 + 0.25 + 1.0
    # Pass 3: the loop's own 1/128 s goes to the phase that closes next.
    assert s.starved("decode", "serve_admit") == 0.0078125 + 0.125
    assert s.starved("decode", "serve_decode_dispatch") == 0.5
    assert s.starved("decode", "serve_fetch") == 0.25
    assert s.starved("decode", "serve_emit") == 1.0
    assert s.starved("decode", "serve_device_wait") == 0.0
    for kind in KINDS:
        assert s.starved(kind) <= s.seconds(kind)


def test_nothing_is_starved_with_nothing_in_service():
    """The device is drained all through a wait; the wait is no pass's."""
    s = _Sched()
    s.wait(1.0)
    s.decode(8, 0.5, 4.0, 0.25)
    s.emit(0.125)
    s.ledger.end_pass()
    starved, seconds = s.starved("decode"), s.seconds("decode")
    assert (starved, seconds) == (0.25 + 0.125, 0.5 + 4.0 + 0.25 + 0.125)
    s.wait(1024.0)
    s.wait(0.5)  # the coalescing sleep
    assert (s.starved("decode"), s.seconds("decode")) == (starved, seconds)
    s.admit(0.25)
    s.chunk(0.5, 0.125)
    s.ledger.end_pass()
    assert s.starved("prefill_only", "serve_admit") == 0.25
    assert s.starved("prefill_only", "serve_prefill_chunk") == 0.5
    assert s.seconds("prefill_only") == 0.875


@pytest.mark.parametrize(
    "ahead, kind",
    [
        ((), "decode"),
        (("row",), "decode"),  # a zero-fill alone is no prefill program
        (("chunk",), "decode_behind_prefill"),
        (("insert",), "decode_behind_prefill"),
        (("row", "chunk", "chunk", "insert"), "decode_behind_prefill"),
    ],
)
def test_a_pass_has_one_kind_by_what_ran_ahead_of_its_chunk(ahead, kind):
    s = _Sched()
    s.wait(1.0)
    with s.tracer.span("serve_emit"):
        for what in ahead:
            s.ledger.fed(what)
    assert s.ledger.ahead == sum(w != "row" for w in ahead)
    s.decode(4, 0.5, 1.0, 0.25)
    s.ledger.fed("insert")  # after the chunk: not ahead of it
    s.ledger.end_pass()
    assert [k for k in KINDS if s.seconds(k)] == [kind]
    assert [k for k in KINDS if s.steps(k)] == [kind] and s.steps(kind) == 4
    assert (s.ledger.decodes, s.ledger.behind) == (1, int(kind != "decode"))
    # The next pass starts clean: no chunk, nothing ahead.
    s.admit(0.5)
    s.ledger.end_pass()
    assert s.seconds("prefill_only") == 0.5 and s.ledger.ahead == 0


def test_a_chain_of_passes_by_hand():
    """The chained order (``_SlotScheduler._run_chunk``): a pass enqueues
    its chunk's successor between the wait and the fetch, and the next
    pass finds its chunk in flight. Of a chained boundary the device is
    starved from the wait's return to the return of the successor's
    dispatch, under ``serve_decode_dispatch``; the fetch and the emit
    behind it book nothing. A pass still has one chunk: the successor is
    the next pass's, which is a ``decode`` pass, counted where it starts,
    with the steps of the chunk it reads. Passes and ``serve_wait`` tile
    the thread's time as before."""
    s = _Sched()
    t0 = s.clock.now
    s.wait(8.0)
    seen = []  # (decodes at the pass's emit, steps booked once it ended)

    def ends():
        seen.append((s.ledger.decodes, s.steps("decode")))
        s.ledger.end_pass()
        seen[-1] = (seen[-1][0], s.steps("decode") - seen[-1][1])

    # Pass 1: enqueues its own chunk (fed since start-up: not starved),
    # then its successor.
    s.admit(0.125)
    s.decode(8, 0.5, 4.0, 0.25, successor=(0.03125, 0.0625, 0.25))
    s.emit(1.0)
    ends()
    # Pass 2: its chunk is in flight; it enqueues the one after.
    s.tick(0.0078125)
    s.decode(8, None, 4.0, 0.25, successor=(0.03125, 0.0625, 0.25))
    s.emit(1.0)
    ends()
    # Pass 3: in flight again, the last of the row's budget: no successor,
    # so the plain order's boundary follows (fetch, emit, admit, dispatch).
    s.tick(0.0078125)
    s.decode(4, None, 2.0, 0.25)
    s.emit(0.5)
    ends()
    # Pass 4: a plain pass.
    s.admit(0.125)
    s.decode(4, 0.5, 2.0, 0.25)
    s.emit(0.5)
    ends()
    s.wait(16.0)
    assert seen == [(1, 8), (2, 8), (3, 4), (4, 4)]  # one chunk a pass
    assert s.ledger.behind == 0
    assert s.seconds("decode") == (
        (0.125 + 0.5 + 0.03125 + 4.0 + 0.0625 + 0.25 + 0.25 + 1.0)
        + (0.0078125 + 0.03125 + 4.0 + 0.0625 + 0.25 + 0.25 + 1.0)
        + (0.0078125 + 2.0 + 0.25 + 0.5)
        + (0.125 + 0.5 + 2.0 + 0.25 + 0.5)
    )
    assert s.seconds("prefill_only") == s.seconds("decode_behind_prefill") == 0.0
    assert sum(s.seconds(k) for k in KINDS) + s.phase_s.value(phase="serve_wait") == s.clock.now - t0
    # The two chained boundaries: the glue and the call, and nothing else;
    # then pass 4's own dispatch, starved as a plain one is.
    assert s.starved("decode", "serve_decode_dispatch") == 2 * (0.0625 + 0.25) + 0.5
    # Passes 3 and 4 alone: a fetch or an emit behind a successor books none.
    assert s.starved("decode", "serve_fetch") == 0.25 + 0.25
    assert s.starved("decode", "serve_emit") == 0.5 + 0.5
    assert s.starved("decode", "serve_admit") == 0.125  # pass 4's
    assert s.starved("decode", "serve_decode_chunk") == 0.0
    assert s.starved("decode", "serve_device_wait") == 0.0
    assert s.starved("decode") == 2 * 0.3125 + 0.5 + 0.5 + 1.0 + 0.125


def test_a_successor_behind_a_prefill_pass_starts_a_plain_decode_pass():
    """The pass that inserts a row runs its chunk behind prefill; where
    its boundary is quiet the successor goes out all the same, and the
    starved seconds of that boundary are that pass's. The pass that reads
    the successor ran nothing ahead of it."""
    s = _Sched()
    s.wait(1.0)
    s.chunk(0.25, 0.125, final=(2.0, 0.5))
    s.emit(0.25, insert_after=0.0625)
    s.decode(8, 0.5, 4.0, 0.25, successor=(0.0, 0.0625, 0.25))
    s.emit(1.0)
    s.ledger.end_pass()
    assert (s.steps("decode_behind_prefill"), s.steps("decode")) == (8, 0)
    assert s.starved("decode_behind_prefill", "serve_decode_dispatch") == 0.3125
    assert s.starved("decode_behind_prefill", "serve_emit") == 0.25  # up to the insert
    assert (s.ledger.decodes, s.ledger.behind) == (2, 1)
    s.decode(8, None, 4.0, 0.25)
    s.emit(1.0)
    s.ledger.fed("insert")  # after the chunk it read: not ahead of it
    s.ledger.end_pass()
    assert (s.steps("decode_behind_prefill"), s.steps("decode")) == (8, 8)
    assert s.seconds("decode") == 4.0 + 0.25 + 1.0
    assert s.starved("decode") == 0.25 + 1.0
    assert (s.ledger.decodes, s.ledger.behind, s.ledger.ahead) == (2, 1, 0)
    # The chain ended: the next pass starts with no chunk of its own.
    s.admit(0.5)
    s.ledger.end_pass()
    assert s.seconds("prefill_only") == 0.5


def test_reset_after_warmup_drops_the_keys_made_ahead_and_zeroes_the_count():
    """Warm-up chains like any traffic: its passes, its count of chained
    chunks and the step keys it left made for its next chunk index are
    gone once the caller resets, so the first live chunk makes index 0's
    own keys."""
    import time

    import jax
    import jax.numpy as jnp

    from tpufw.infer import SamplingConfig
    from tpufw.models import LLAMA_CONFIGS, Llama

    model = Llama(LLAMA_CONFIGS["llama3_tiny"].decode_config())
    params = jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    metrics = serve_mod._Metrics()
    sched = serve_mod._SlotScheduler(
        model, params, eos_id=None, seed_base=0, metrics=metrics,
        default_sampling=SamplingConfig(temperature=0.0),
    )
    chained = metrics.registry.counter("tpufw_serve_chunks_chained_total")
    steps = metrics.registry.counter("tpufw_serve_pass_steps_total")
    assert "tpufw_serve_chunks_chained_total 0" in metrics.registry.render()
    def booked():
        return sum(steps.value(**{"pass": k}) for k in KINDS)

    warm = sched.submit([[1, 2, 3]], 49, None)[0]  # 48 steps: 16, 16, 16
    assert chained.value() == 2 and sched._chunk_index == 3
    deadline = time.monotonic() + 60  # the reply leaves before the pass ends
    while booked() < 48 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert booked() == 48
    sched._keys_ahead = ((3, 16), "made for an index the reset takes back")
    sched.reset_after_warmup()
    assert sched._keys_ahead is None and sched._chunk_index == 0
    assert chained.value() == 0
    assert booked() == 0
    # Seed replay: the same request again draws chunk indices 0, 1, 2.
    assert sched.submit([[1, 2, 3]], 49, None)[0] == warm
    assert chained.value() == 2 and sched._chunk_index == 3


def test_a_stalled_pass_without_a_chunk_is_prefill_only():
    s = _Sched()
    s.wait(1.0)
    with s.tracer.span("serve_prefill_chunk", final=False):
        s.tick(0.25)  # stalled on pages: nothing dispatched
    s.tick(0.0009765625)  # the loop's sleep, 2**-10 s
    s.ledger.end_pass()
    assert s.seconds("prefill_only") == 0.2509765625 and s.steps("prefill_only") == 0
    assert s.seconds("decode") == s.seconds("decode_behind_prefill") == 0.0


def test_a_speculative_pass_counts_its_verify_block():
    s = _Sched()
    s.wait(1.0)
    s.decode(4, 0.5, 1.0, 0.25, name="serve_spec_chunk")
    s.ledger.end_pass()
    assert s.steps("decode") == 5


def test_a_blocking_prefill_is_fed_from_its_start_and_drained_at_its_end():
    """``_admit_job``: the whole-prompt prefill dispatches and reads inside
    one span, so its seconds are never starved (a lower bound), and the
    decode chunk of its pass runs behind prefill."""
    s = _Sched()
    s.wait(1.0)
    s.decode(8, 0.5, 1.0, 0.25)
    s.emit(0.125)
    s.ledger.end_pass()
    with s.tracer.span("serve_admit", queued=1):
        s.tick(0.5)
        s.ledger.fed("chunk")
        with s.tracer.span("serve_prefill", prompt=8, width=64):
            s.tick(4.0)
        s.tick(0.25)  # drained again until the insert returns
        s.ledger.fed("insert")
        s.tick(0.0625)
    s.decode(8, 0.5, 1.0, 0.25)
    s.ledger.end_pass()
    assert s.starved("decode_behind_prefill", "serve_admit") == 0.5 + 0.25
    assert s.starved("decode_behind_prefill", "serve_prefill") == 0.0
    assert s.starved("decode_behind_prefill") == 0.5 + 0.25 + 0.25


def test_a_pool_just_built_leaves_the_device_fed():
    s = _Sched()
    s.wait(1.0)
    s.decode(8, 0.5, 1.0, 0.25)
    s.ledger.end_pass()
    with s.tracer.span("serve_pool_build", cache_len=256, slots=8):
        s.tick(2.0)
    s.admit(0.5)  # not starved: the ledger cannot know
    s.ledger.end_pass()
    assert s.starved("prefill_only") == 2.0  # up to the build's end
    assert s.starved("prefill_only", "serve_admit") == 0.0


def test_reset_voids_the_pass_that_is_running_and_no_other():
    """Warm-up's last pass may still be running when the caller resets:
    it books nothing when it ends; the next one does."""
    s = _Sched()
    s.wait(1.0)
    s.decode(8, 0.5, 1.0, 0.25)
    s.ledger.end_pass()
    s.decode(8, 0.5, 1.0, 0.25)
    s.ledger.reset()  # from the caller's thread, mid-pass
    s.emit(0.5)
    s.ledger.end_pass()
    assert [s.seconds(k) + s.steps(k) + s.starved(k) for k in KINDS] == [0.0, 0.0, 0.0]
    s.wait(2.0)
    s.ledger.reset()  # at rest: the next pass is a real one
    s.decode(16, 0.5, 1.0, 0.25)
    s.ledger.end_pass()
    assert (s.seconds("decode"), s.steps("decode")) == (1.75, 16)
    assert (s.ledger.decodes, s.ledger.behind) == (3, 0)  # a row's record is not reset


def test_every_series_is_exposed_at_zero_from_the_start():
    s = _Sched()
    text = s.reg.render()
    for kind in KINDS:
        assert f'tpufw_serve_pass_seconds_total{{pass="{kind}"}} 0' in text
        assert f'tpufw_serve_pass_steps_total{{pass="{kind}"}} 0' in text
        for phase in serve_mod.SCHED_PHASES[1:]:
            assert (
                f'tpufw_serve_pass_starved_seconds_total{{pass="{kind}",phase="{phase}"}} 0'
            ) in text
    assert "serve_fetch" in serve_mod.SCHED_PHASES and serve_mod.SCHED_PHASES[0] == "serve_wait"


def test_without_a_registry_it_still_counts_for_req_decode():
    clock = _HandClock()
    tracer = trace_mod.Tracer(None, clock=clock)
    ledger = serve_mod._PassLedger(tracer, None, clock=clock)
    ledger.fed("chunk")
    ledger.fed("decode")
    ledger.end_pass()
    ledger.reset()
    assert (ledger.decodes, ledger.behind, ledger.ahead) == (1, 1, 0)


def test_reset_from_another_thread_never_lets_an_older_pass_book():
    """The caller resets while the scheduler's thread ends passes (the
    server's warm-up does): whatever the interleaving, a pass that began
    before a reset books nothing after it, so right after a reset the
    kinds' seconds never exceed the time gone by since."""
    import sys
    import threading
    import time

    tracer = trace_mod.Tracer(None)
    reg = Registry()
    ledger = serve_mod._PassLedger(tracer, reg)
    seconds = reg.counter("tpufw_serve_pass_seconds_total")
    stop, over = threading.Event(), []

    def passes():
        while not stop.is_set():
            with tracer.span("serve_decode_chunk", k=1):
                ledger.fed("decode")
                time.sleep(0.002)
            ledger.end_pass()

    def resets():
        while not stop.is_set():
            t = time.perf_counter()
            ledger.reset()
            time.sleep(0.0005)
            booked = sum(seconds.value(**{"pass": k}) for k in KINDS)
            gone = time.perf_counter() - t
            if booked > gone + 1e-4:
                over.append((booked, gone))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=passes)] + [
            threading.Thread(target=resets) for _ in range(3)
        ]
        for t in threads:
            t.start()
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not over, over[:3]
    assert ledger.decodes > 10
