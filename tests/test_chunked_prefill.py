"""Chunked prefill (tpufw.infer.pages ``_prefill_chunk_jit`` family +
the slot scheduler's mixed prefill+decode pools).

Contracts, all on CPU with the tiny model:

- PARITY: a prompt prefilled one page-aligned chunk at a time — any
  chunk size, bf16 or int8 pool — samples the exact first token and
  decodes the exact greedy continuation of the monolithic
  ``prefill_row`` path, and its row cache is bit-equal over the
  prompt span (right-padded tail positions are masked to segment 0,
  so their logits exp-underflow to exactly 0.0).
- RESUME: abandoning a chunked prefill mid-flight leaves its
  completed full pages checkpointed in the prefix trie; a
  re-admission of the same prompt resumes from the last full page
  (``shared_n`` > 0, fewer chunks run) with ZERO token divergence.
- SHAPE STABILITY: chunk programs key on (width, pool, quant) only —
  chunk-COUNT variation and page churn add zero retraces
  (TRACE_COUNTS["prefill_chunk"] is pinned).
- ROW CANVAS: a pool traces its row model ONCE, when it is built, for
  the shapes of the B=1 row cache; an admission only fills fresh
  zeros from them (never a cached array: the chunk and attach jits
  donate the row leaves), equal to what the per-admission trace made,
  and builds no program — for K/V heads, MLA latents and per-slot
  state leaves alike.
- FUNGIBILITY: a scheduler admitting prompts chunk-by-chunk inside
  the same passes that advance decoding slots (mixed pools, no
  separate tick) emits byte-identical outputs to the monolithic
  scheduler, including under concurrent submission.
- NO HOL: a 1-page prompt submitted AFTER a 10-page prompt streams
  its first token before the long prompt finishes prefilling.
- ON THE RECORD: the scheduler's leaf phases partition its thread's
  time (self seconds, in the trace and on
  ``tpufw_serve_phase_seconds_total`` alike), the row allocation is
  recorded once per admission, and a request's ``req_queue`` /
  ``req_prefill`` spans and ``serve_request`` event share its ``rid``.
"""

import dataclasses
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.infer import SamplingConfig
from tpufw.infer import pages as pages_mod
from tpufw.infer import slots as slots_mod
from tpufw.models import LLAMA_CONFIGS, Llama

GREEDY = SamplingConfig(temperature=0.0)
MAX_NEW = 6
PAGE = 16
N_SLOTS = 4

PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3, 8, 4,
          6, 2, 6, 4, 3, 3, 8, 3, 2, 7, 9, 5, 0, 2, 8, 8]  # 36 tokens


@pytest.fixture(scope="module")
def tiny_paged():
    base = LLAMA_CONFIGS["llama3_tiny"].decode_config()
    cfg = dataclasses.replace(base, max_seq_len=64)
    row_model = Llama(cfg)
    params = jax.jit(row_model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, row_model, params


def _paged_pool(cfg, row_model, params, kv_quant=""):
    pcfg = dataclasses.replace(
        cfg,
        kv_page=PAGE,
        kv_pages=N_SLOTS * (cfg.max_seq_len // PAGE) + 1,
        kv_quant=kv_quant,
    )
    return pages_mod.PagedSlotPool.create_paged(
        Llama(pcfg), row_model, params, N_SLOTS,
        sampling=GREEDY, eos_id=None,
    )


def _decode_all(pool, firsts, max_new=MAX_NEW, chunk=2):
    rows = {i: [fi] for i, fi in firsts.items()}
    ci = 0
    while any(len(t) < max_new for t in rows.values()):
        key = jax.random.fold_in(jax.random.key(1), ci)
        ci += 1
        out = np.asarray(pool.decode_steps(jax.random.split(key, chunk)))
        for i in rows:
            take = min(chunk, max_new - len(rows[i]))
            rows[i].extend(out[i, :take].tolist())
    return rows


def _monolithic(pool, prompt, rng):
    """Reference admission: acquire + prefill_row + insert. Returns
    (row_cache, first_int) with slot 0 occupied."""
    ids, shared = pool.acquire_pages(prompt, len(prompt) + MAX_NEW - 1)
    assert shared == 0
    cache, _f, first, _d, seen = slots_mod.prefill_row(
        pool.row_model, pool.params, prompt, rng,
        sampling=GREEDY, eos_id=None, pad_to=len(prompt),
    )
    pool.insert_paged(
        0, cache, first, len(prompt), MAX_NEW - 1, ids, 0, row_seen=seen
    )
    return cache, first


def _chunked(pool, prompt, rng, chunk_pages):
    """Chunked admission to completion. Returns the ChunkedPrefill
    with slot 0 occupied (finalized)."""
    cp = pool.start_chunked(
        prompt, len(prompt) + MAX_NEW - 1, rng, chunk_pages
    )
    while True:
        status = pool.chunk_step(cp)
        assert status != "stalled"
        if status == "done":
            break
    pool.finalize_chunked(0, cp, MAX_NEW - 1)
    return cp


# ---------------------------------------------------------- parity

@pytest.mark.parametrize("kv_quant", ["", "int8"])
@pytest.mark.parametrize("chunk_pages", [1, 2])
def test_chunked_bit_equal_monolithic(tiny_paged, kv_quant, chunk_pages):
    cfg, row_model, params = tiny_paged
    rng = jax.random.fold_in(jax.random.key(0), 0)

    pool_a = _paged_pool(cfg, row_model, params, kv_quant)
    _cache, first_a = _monolithic(pool_a, PROMPT, rng)
    ref = _decode_all(pool_a, {0: first_a})[0]

    pool_b = _paged_pool(cfg, row_model, params, kv_quant)
    cp = _chunked(pool_b, PROMPT, rng, chunk_pages)
    assert cp.first_int == first_a
    got = _decode_all(pool_b, {0: cp.first_int})[0]
    assert got == ref


def test_chunked_row_cache_bit_equal(tiny_paged):
    """Contiguous-level assertion: the chunk-built row cache matches
    ``prefill_row``'s bit-for-bit over the prompt span (and exactly
    on the cursor), not merely in its sampled tokens."""
    cfg, row_model, params = tiny_paged
    rng = jax.random.fold_in(jax.random.key(0), 0)
    pool = _paged_pool(cfg, row_model, params)
    cp = pool.start_chunked(PROMPT, len(PROMPT) + MAX_NEW - 1, rng, 2)
    while pool.chunk_step(cp) != "done":
        pass
    ref_cache, _f, first, _d, _s = slots_mod.prefill_row(
        pool.row_model, pool.params, PROMPT, rng,
        sampling=GREEDY, eos_id=None, pad_to=len(PROMPT),
    )
    assert cp.first_int == int(np.asarray(first).reshape(-1)[0])
    rp, rnames, rleaves, _ = pages_mod._flatten_with_names(cp.row_cache)
    mp, _mn, mleaves, _ = pages_mod._flatten_with_names(ref_cache)
    assert rp == mp
    p = len(PROMPT)
    for name, a, b in zip(rnames, rleaves, mleaves):
        a, b = np.asarray(a), np.asarray(b)
        if name == "cache_index":
            assert (a == b).all(), name
        elif name == "cached_segment_ids":
            assert (a[..., :p] == b[..., :p]).all(), name
        else:
            ca = pages_mod._collapse_row(a, a.ndim - 1)
            cb = pages_mod._collapse_row(b, b.ndim - 1)
            assert (ca[:, :p] == cb[:, :p]).all(), name


# ---------------------------------------------------------- resume

def test_resume_from_trie_checkpoint(tiny_paged):
    cfg, row_model, params = tiny_paged
    rng = jax.random.fold_in(jax.random.key(0), 0)

    pool_a = _paged_pool(cfg, row_model, params)
    _cache, first_a = _monolithic(pool_a, PROMPT, rng)
    ref = _decode_all(pool_a, {0: first_a})[0]

    pool = _paged_pool(cfg, row_model, params)
    cp = pool.start_chunked(PROMPT, len(PROMPT) + MAX_NEW - 1, rng, 1)
    assert pool.chunk_step(cp) == "ran"
    assert pool.chunk_step(cp) == "ran"  # 2 full pages committed
    pool.abandon_chunked(cp)
    # The two completed pages survive the abandon as trie checkpoints.
    cp2 = pool.start_chunked(PROMPT, len(PROMPT) + MAX_NEW - 1, rng, 1)
    assert cp2.resumed and cp2.shared_n == 2
    n_chunks = 0
    while pool.chunk_step(cp2) != "done":
        n_chunks += 1
    # 36 tokens = 3 pages total; 2 resumed, so a single final chunk.
    assert n_chunks == 0
    assert cp2.first_int == first_a
    pool.finalize_chunked(0, cp2, MAX_NEW - 1)
    got = _decode_all(pool, {0: cp2.first_int})[0]
    assert got == ref  # zero token divergence after resume


# ------------------------------------------------- shape stability

def test_zero_retrace_across_chunk_count(tiny_paged):
    cfg, row_model, params = tiny_paged
    rng = jax.random.fold_in(jax.random.key(0), 0)
    pool = _paged_pool(cfg, row_model, params)
    _chunked(pool, PROMPT, rng, 1)  # 36 tokens -> 3 chunk calls
    pool.release_slot(0)
    before = pages_mod.TRACE_COUNTS["prefill_chunk"]
    # Different prompt length, different chunk count, page churn from
    # the release above — same (width, pool, quant) program keys.
    _chunked(pool, [7, 5] * 10, rng, 1)  # 20 tokens -> 2 chunk calls
    assert pages_mod.TRACE_COUNTS["prefill_chunk"] == before


# ------------------------------------------------------ row canvas

def _row_family(kind):
    """(model class, decode config) of one cache kind the row twin
    carries: K/V heads, MLA latents, per-slot state beside K/V, window
    layers' rings beside K/V."""
    if kind == "gqa":
        from tpufw.models.mixtral import MIXTRAL_CONFIGS, Mixtral

        return Mixtral, MIXTRAL_CONFIGS["mixtral_tiny"]
    if kind == "mla":
        from tpufw.models.deepseek import DEEPSEEK_CONFIGS, Deepseek

        return Deepseek, DEEPSEEK_CONFIGS["deepseek_tiny"]
    if kind == "window":
        from tpufw.models.laguna import LAGUNA_CONFIGS, Laguna

        return Laguna, LAGUNA_CONFIGS["laguna_tiny"]
    from tpufw.models.solar_open2 import SOLAR_OPEN2_CONFIGS, SolarOpen2

    return SolarOpen2, SOLAR_OPEN2_CONFIGS["solar_open2_tiny"]


@pytest.fixture(scope="module", params=["gqa", "mla", "state", "window"])
def family(request):
    cls, base = _row_family(request.param)
    cfg = dataclasses.replace(base.decode_config(), max_seq_len=64)
    row_model = cls(cfg)
    params = jax.jit(row_model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    def pool(n_slots=N_SLOTS):
        pcfg = dataclasses.replace(
            cfg, kv_page=PAGE, kv_pages=n_slots * (64 // PAGE) + 1
        )
        return pages_mod.PagedSlotPool.create_paged(
            cls(pcfg), row_model, params, n_slots,
            sampling=GREEDY, eos_id=None,
        )

    return request.param, row_model, params, pool


def _prompt_of(i, n=36):
    """36 tokens = 2 full pages + 4; no page shared between two ``i``."""
    return [(t + 17 * i) % 200 + 1 for t in (PROMPT * 2)[:n]]


def _admit_chunked(pool, slot, prompt):
    cp = pool.start_chunked(
        prompt, len(prompt) + MAX_NEW - 1, jax.random.key(2), 1
    )
    while pool.chunk_step(cp) != "done":
        pass
    pool.finalize_chunked(slot, cp, MAX_NEW - 1)
    return cp


def _per_call_row_zeros_tree(row_model, params, home):
    """What ``_attach_row`` computed on EVERY admission before the
    shapes were kept (``pages._row_zeros_tree``, verbatim): the oracle
    for the tree an admission gets now."""

    def init(p):
        toks = jnp.zeros((1, 1), jnp.int32)
        pos = jnp.zeros((1, 1), jnp.int32)
        seg = jnp.ones((1, 1), jnp.int32)
        _, vars_ = row_model.apply(
            {"params": p}, toks, positions=pos, segment_ids=seg,
            mutable=["cache"],
        )
        return vars_["cache"]

    shapes = jax.eval_shape(init, params)
    return {
        "cache": jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, l.dtype, device=home), shapes
        )
    }


def test_admissions_never_trace_the_row_model_again(family, monkeypatch):
    _, row_model, _, make_pool = family
    pool = make_pool()
    assert pool.row_shape_traces == 1
    _admit_chunked(pool, 0, _prompt_of(0))  # traces the chunk program
    calls = []
    real_apply = type(row_model).apply

    def counting_apply(self, *a, **kw):
        calls.append(self)
        return real_apply(self, *a, **kw)

    monkeypatch.setattr(type(row_model), "apply", counting_apply)
    for slot in (1, 2, 3):
        _admit_chunked(pool, slot, _prompt_of(slot, n=20 + 8 * slot))
    assert not calls, f"{len(calls)} host traces over 3 admissions"
    assert pool.row_shape_traces == 1


def test_row_canvas_equals_the_per_call_tree(family):
    kind, row_model, params, make_pool = family
    pool = make_pool()
    want = _per_call_row_zeros_tree(row_model, params, pool.home)
    got = pool._attach_row([])
    assert (
        jax.tree_util.tree_structure(got)
        == jax.tree_util.tree_structure(want)
        == jax.tree_util.tree_structure(pool.row_shapes)
    )
    names = {
        pages_mod._leaf_name(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(got)[0]
    }
    assert {
        "gqa": {"cached_key", "cached_value"},
        "mla": {"cached_ckv", "cached_kpe"},
        "state": {"cached_key", "cached_value", "kda_state", "conv_state"},
        "window": {"cached_key", "cached_value", "ring_key", "ring_value",
                   "ring_slot", "ring_segment"},
    }[kind] <= names
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        assert g.sharding == w.sharding == pool.home
        assert g.committed and w.committed
        assert not np.asarray(g).any()
    # Live buffers of its own every call: the first tree's are gone
    # (as after a donation) and the second is untouched.
    again = pool._attach_row([])
    for g in jax.tree_util.tree_leaves(got):
        g.delete()
    for g in jax.tree_util.tree_leaves(again):
        assert not np.asarray(g).any()


def test_admissions_in_a_row_get_live_buffers_and_the_same_tokens(family):
    kind, _, _, make_pool = family
    pa = _prompt_of(0)
    pool = make_pool()
    # Two chunked admissions in a row: the second starts after every
    # leaf the first was handed has been donated, chunk after chunk.
    cp_a = _admit_chunked(pool, 0, pa)
    _admit_chunked(pool, 1, _prompt_of(1))
    got = _decode_all(pool, {0: cp_a.first_int})[0]
    ref = make_pool()
    _, first = _monolithic(ref, pa, jax.random.key(2))
    assert cp_a.first_int == first
    assert got == _decode_all(ref, {0: first})[0]
    if kind in ("state", "window"):
        assert pool.prefix is None  # no shared pages beside state or rings
        return
    # Two prefix hits in a row (the first admission's pages are in the
    # trie): each attach donates a canvas of its own.
    for slot, tail in ((2, [7, 9, 4]), (3, [11, 3])):
        prompt = pa[:32] + tail
        cp = _admit_chunked(pool, slot, prompt)
        assert cp.shared_n == 2 and cp.n_chunks == 1
        cold = make_pool()
        _, first = _monolithic(cold, prompt, jax.random.key(2))
        assert cp.first_int == first
        assert (
            _decode_all(pool, {slot: first}, max_new=3)[slot]
            == _decode_all(cold, {0: first}, max_new=3)[0]
        )


def test_a_second_admission_builds_no_program(family):
    from benchmarks.compile_log import CompileLog

    _, _, _, make_pool = family
    log = CompileLog()
    # A pool size no other test of this file has: its programs are
    # built here, whatever ran before in this process.
    pool = make_pool(n_slots=3)
    assert any("row_zeros" in name for name, _ in log.programs)
    built = log.n
    _admit_chunked(pool, 0, _prompt_of(0))  # three chunks
    first = [name for name, _ in log.programs[built:]]
    # The canvas and the chunk program's own donated output are the
    # same argument to jit: one chunk program over the three chunks.
    assert sum("prefill_chunk" in name for name in first) == 1, first
    built = log.n
    _admit_chunked(pool, 1, _prompt_of(1, n=20))
    _admit_chunked(pool, 2, _prompt_of(2))
    assert log.n == built, log.programs[built:]


# ------------------- a frozen row through a whole further chunk (PR 40)

def _arena(pool):
    """The pool's arena leaves (pages, segment ids, scales) as
    ``[stacks, n_pages, page, ...]`` host arrays, by leaf path."""
    from tpufw.ops.kv_store import role

    paths, names, leaves, _ = pool._pool_flat()
    return {
        path: np.asarray(pages_mod._collapse_arena(leaf, role(name).rank))
        for path, name, leaf in zip(paths, names, leaves)
        if role(name).in_arena
    }


@pytest.mark.parametrize("kv_quant", ["", "int8"])
def test_a_frozen_row_rides_a_further_chunk_on_its_own_tail(tiny_paged, kv_quant):
    """The slot scheduler's chained order steps a row that has ended
    through one more whole chunk before its slot is released
    (``serve._SlotScheduler._run_chunk``). Here the row ends on the last
    step of a chunk with its cursor on the LAST key slot of its cache
    (36 + 28 = 64 = max_seq_len: Mixtral's 8,064 + 128 = 8,192 at small
    size), beside a live row. Through the further chunk it emits pad,
    and of the arena it writes the junk page and its own last key slot,
    which holds no token of it: every slot under its last cursor, every
    other row's page and every free page stay byte for byte, and the
    live row decodes the tokens it decodes once the frozen row's slot
    was released."""
    cfg, row_model, params = tiny_paged
    s = cfg.max_seq_len
    live_prompt = [2, 7, 1, 8, 2, 8]

    def seated(release: bool):
        pool = _paged_pool(cfg, row_model, params, kv_quant)
        for slot, (prompt, max_new) in enumerate(
            ((PROMPT, s - len(PROMPT)), (live_prompt, 50))
        ):
            cp = pool.start_chunked(
                prompt, len(prompt) + max_new - 1,
                jax.random.fold_in(jax.random.key(0), slot), 1,
            )
            while pool.chunk_step(cp) != "done":
                pass
            pool.finalize_chunked(slot, cp, max_new - 1)
        # 27 steps: the long row's budget, spent on the chunk's last step.
        outs = [
            np.asarray(pool.decode_steps(jax.random.split(jax.random.key(i), 9)))
            for i in range(3)
        ]
        assert bool(np.asarray(pool.done)[0]) and not bool(np.asarray(pool.done)[1])
        assert int(np.asarray(pool.remaining)[0]) == 0
        assert all((o[0] != 0).any() for o in outs)  # it was live to the end
        own = list(pool.slot_pages[0])
        if release:
            pool.release_slot(0)
        return pool, own

    pool, own = seated(release=False)
    assert len(own) == s // PAGE  # it holds its whole row
    before = _arena(pool)
    further = np.asarray(pool.decode_steps(jax.random.split(jax.random.key(9), 8)))
    after = _arena(pool)
    assert (further[0] == 0).all()  # frozen: pad, eight times
    live_pages = set(pool.slot_pages[1])
    for path, a in after.items():
        b = before[path]
        changed = {
            int(p) for p in np.nonzero(
                (a != b).reshape(a.shape[0], a.shape[1], -1).any(axis=(0, 2))
            )[0]
        }
        # The junk page, the live row's own, the frozen row's last.
        assert changed <= {0} | live_pages | {own[-1]}, (path, changed)
        # Of its last page, the last key slot alone: no token of the
        # row's lives there (its last one was emitted, never written).
        assert np.array_equal(a[:, own[-1], : PAGE - 1], b[:, own[-1], : PAGE - 1]), path
    # The live row's tokens are those it decodes beside a released slot.
    ref, _ = seated(release=True)
    ref_further = np.asarray(ref.decode_steps(jax.random.split(jax.random.key(9), 8)))
    assert further[1].tolist() == ref_further[1].tolist()
    assert (further[1] != 0).any()
    # And the release that follows frees the row's pages, once (its
    # prompt's two full pages stay with the prefix trie).
    free0, kept = pool.allocator.n_free, len(PROMPT) // PAGE
    assert pool.release_slot(0) == len(own) - kept
    assert pool.allocator.n_free == free0 + len(own) - kept
    assert len(set(pool.allocator.free)) == len(pool.allocator.free)


# ---------------------------------------------- scheduler fungibility

def _scheduler(model, params, prefill_chunk_pages):
    from tpufw.workloads import serve as serve_mod

    return serve_mod._SlotScheduler(
        model, params, eos_id=None, default_sampling=GREEDY,
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=True,
        prefill_chunk_pages=prefill_chunk_pages,
    )


@pytest.fixture(scope="module")
def tiny_sched_model():
    base = LLAMA_CONFIGS["llama3_tiny"].decode_config()
    cfg = dataclasses.replace(base, max_seq_len=256)
    model = Llama(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def test_mixed_pool_pass_parity(tiny_sched_model):
    """Concurrent chunked admissions interleave with decoding slots
    inside the same passes — outputs must match the monolithic
    scheduler's exactly (same rng streams, greedy)."""
    model, params = tiny_sched_model
    prompts = [[i + 1, 5, 9, 2, 6] * 8 for i in range(3)]  # 40 tokens

    s_mono = _scheduler(model, params, prefill_chunk_pages=0)
    ref = [s_mono.submit([p], 8)[0][0] for p in prompts]

    s_seq = _scheduler(model, params, prefill_chunk_pages=1)
    assert [s_seq.submit([p], 8)[0][0] for p in prompts] == ref

    s_conc = _scheduler(model, params, prefill_chunk_pages=1)
    results = {}

    def run(i, p):
        results[i] = s_conc.submit([p], 8)[0][0]

    threads = [
        threading.Thread(target=run, args=(i, p))
        for i, p in enumerate(prompts)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert [results[i] for i in range(3)] == ref


def test_long_prompt_no_hol(tiny_sched_model):
    """Regression: a 1-page prompt submitted after a 10-page prompt
    must stream its first token before the long prompt's — under
    monolithic admission it is head-of-line blocked behind the whole
    long prefill."""
    model, params = tiny_sched_model
    s = _scheduler(model, params, prefill_chunk_pages=1)
    long_p = [7, 3] * 80  # 160 tokens = 10 chunk passes
    short_p = [1, 2, 3, 4, 5, 6, 7, 8]
    ql: "queue.Queue" = queue.Queue()
    qs: "queue.Queue" = queue.Queue()
    s.submit_stream([long_p], 8, GREEDY, ql)
    time.sleep(0.01)
    s.submit_stream([short_p], 8, GREEDY, qs)

    def drain(q):
        first = None
        while True:
            kind, payload = q.get(timeout=120)
            if kind == "chunk" and first is None and any(payload):
                first = time.perf_counter()
            if kind in ("done", "error"):
                return first, kind

    out = {}
    tl = threading.Thread(target=lambda: out.setdefault("l", drain(ql)))
    ts = threading.Thread(target=lambda: out.setdefault("s", drain(qs)))
    tl.start()
    ts.start()
    tl.join()
    ts.join()
    (long_first, long_kind) = out["l"]
    (short_first, short_kind) = out["s"]
    assert long_kind == "done" and short_kind == "done"
    assert short_first < long_first


# ------------------------------------- the scheduler's pass on the record

def _wait_idle(tracer, timeout=30.0):
    """Until the scheduler thread is back in ``serve_wait`` and nothing
    else is open: every span of the work before it has been recorded."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        stacks = list(tracer.live_spans().values())
        if [[n for n, _ in st] for st in stacks] == [["serve_wait"]]:
            return
        time.sleep(0.005)
    raise AssertionError(f"scheduler never went idle: {tracer.live_spans()}")


def test_scheduler_phases_and_request_chain(tiny_sched_model, tmp_path):
    """Paged chunked prefill with the pass on the record: the leaf
    phases partition the scheduler thread's own spans (they nest, and
    their self seconds sum to the outermost spans' durations, in the
    trace and on the counter alike; nothing here is held to the wall
    clock of a loaded machine), the row
    allocation is recorded once per admission, the request-chain
    histograms each take one observation per request, and one request's
    ``req_queue`` / ``req_prefill`` spans and ``serve_request`` event
    share its ``rid``."""
    from tpufw.obs import events as events_mod
    from tpufw.obs import trace as trace_mod
    from tpufw.workloads import serve as serve_mod

    model, params = tiny_sched_model
    tracer = trace_mod.Tracer(str(tmp_path / "trace-serve.json"))
    events = events_mod.EventLog(str(tmp_path / "events.jsonl"))
    metrics = serve_mod._Metrics()
    sched = serve_mod._SlotScheduler(
        model, params, eos_id=None, default_sampling=GREEDY, seed_base=0,
        page=PAGE, arena_pages=None, prefix_cache=True,
        prefill_chunk_pages=1, metrics=metrics, events=events,
        tracer=tracer,
    )
    phases = set(serve_mod.SCHED_PHASES)

    def batch(first_tokens):
        # 40 tokens = chunks of 16, 16 and a padded 8; distinct first
        # tokens, so no prompt shares a page with another.
        prompts = [[t, 5, 9, 2, 6] * 8 for t in first_tokens]
        threads = [
            threading.Thread(target=sched.submit, args=([p], 8))
            for p in prompts
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
        _wait_idle(tracer)

    traces = metrics.registry.counter("tpufw_serve_row_shape_traces_total")
    assert traces.value() == 0  # exposed before any pool is built
    batch([1, 2, 3])  # builds the pool and every program
    assert traces.value() == 1  # the one pool built
    n0 = len(tracer._events)
    batch([4, 5, 6])
    assert traces.value() == 1  # three more admissions, no trace
    warm = [e for e in tracer._events[n0:] if e["ph"] == "X"]
    sched_evs = [e for e in warm if e["name"] in phases]

    # The phases partition the thread's own spans over the warm batch:
    # they nest, the outermost ones follow one another without overlap,
    # and the self seconds of all of them sum to the outermost ones'
    # durations. What lies BETWEEN two outermost spans is the thread off
    # the CPU or between two ``with`` blocks, which grows with the
    # machine's load (six workers of a test run: the share this test
    # used to bound from below), so it is bounded from above only.
    lo = min(e["ts"] for e in sched_evs)
    hi = max(e["ts"] + e["dur"] for e in sched_evs)
    covered = sum(e["self_dur"] for e in sched_evs)
    assert covered <= (hi - lo) * (1 + 1e-6)
    assert len({e["tid"] for e in sched_evs}) == 1, "one scheduler thread"
    outer, end = [], -1.0
    for e in sorted(sched_evs, key=lambda e: (e["ts"], -e["dur"])):
        if e["ts"] + e["dur"] <= end + 1e-2:
            continue  # inside the outermost span before it: nested
        assert e["ts"] >= end - 1e-2, (e["name"], e["ts"], end)
        outer.append(e)
        end = e["ts"] + e["dur"]
    assert {e["name"] for e in outer} <= phases and len(outer) < len(sched_evs)
    # Each event's times are rounded to a nanosecond.
    assert covered == pytest.approx(
        sum(e["dur"] for e in outer), abs=2e-3 * len(sched_evs)
    )
    by_phase = {}
    for e in sched_evs:
        by_phase[e["name"]] = by_phase.get(e["name"], 0) + 1
    for name in ("serve_wait", "serve_admit", "serve_prefill_chunk",
                 "serve_decode_chunk", "serve_decode_dispatch",
                 "serve_device_wait", "serve_emit"):
        assert by_phase.get(name), f"{name} never recorded: {by_phase}"
    # Once per admission, inside the request's first chunk.
    assert by_phase["serve_row_alloc"] == 3
    assert by_phase["serve_prefill_chunk"] == 9
    chunk_args = [e["args"] for e in sched_evs if e["name"] == "serve_prefill_chunk"]
    assert {a["width"] for a in chunk_args} == {PAGE}
    assert sum(a["final"] for a in chunk_args) == 3
    admitted = [e["args"]["admitted"] for e in sched_evs if e["name"] == "serve_admit"]
    assert sum(admitted) == 3

    # The counter carries the same self seconds as the trace (all six
    # requests: nothing here resets it).
    reg = metrics.registry
    counter = reg.counter("tpufw_serve_phase_seconds_total")
    traced = {}
    for e in tracer._events:
        if e["ph"] == "X" and e["name"] in phases:
            traced[e["name"]] = traced.get(e["name"], 0.0) + e["self_dur"] / 1e6
    for name in serve_mod.SCHED_PHASES:
        assert counter.value(phase=name) == pytest.approx(
            traced.get(name, 0.0), abs=1e-4
        ), name
    text = reg.render()
    for name in serve_mod.SCHED_PHASES:  # exposed even where still 0
        assert f'tpufw_serve_phase_seconds_total{{phase="{name}"}} ' in text
    assert "\ntpufw_serve_row_shape_traces_total 1\n" in text

    # The request chain: one observation per request in each histogram.
    for hist in ("join_latency", "queue_wait", "prefill"):
        assert reg.histogram(f"tpufw_serve_{hist}_seconds").value() == 6, hist

    # One identifier through a request's records.
    events.close()
    done = [e for e in events_mod.read_events(events.path) if e["kind"] == "serve_request"]
    queued = {e["args"]["rid"]: e for e in warm if e["name"] == "req_queue"}
    prefilled = {e["args"]["rid"]: e for e in warm if e["name"] == "req_prefill"}
    assert set(queued) == set(prefilled) == {4, 5, 6}
    assert {e["rid"] for e in done} == {1, 2, 3, 4, 5, 6}
    for rid, ev in prefilled.items():
        assert ev["args"]["prompt"] == queued[rid]["args"]["prompt"] == 40
        assert ev["args"]["chunks"] == 3
        assert ev["args"]["passes"] >= 3
        # Request-level records cross passes: no phase, no counter.
        assert ev["name"] not in phases


def _resting_since(tracer):
    """When the ``serve_wait`` the idle scheduler sits in was opened, on
    the tracer's clock (call after ``_wait_idle``)."""
    ((frame,),) = tracer._live.values()
    assert frame[0] == "serve_wait"
    return frame[1]


def test_pass_ledger_on_a_toy_server(tiny_sched_model, tmp_path):
    """The scheduler books every pass (``serve._PassLedger``; its exact
    identities are held on a hand-set clock in tests/test_pass_ledger.py).
    A row decodes through a neighbour's admission: every pass has one
    kind, the one whose decode chunk ran behind a chunk program or insert
    is ``decode_behind_prefill`` and no other is, the steps of the kinds
    are the chunks' k, passes and ``serve_wait`` tile the thread's time
    between two moments of rest, starved seconds stay under each kind's
    own, the wait is split from the fetch, and a request's three legs
    (``req_queue``, ``req_prefill``, ``req_decode``) carry one ``rid``,
    ``req_decode`` with the decode passes the row sat in."""
    from tpufw.obs import trace as trace_mod
    from tpufw.workloads import serve as serve_mod

    model, params = tiny_sched_model
    tracer = trace_mod.Tracer(str(tmp_path / "trace-serve.json"))
    metrics = serve_mod._Metrics()
    reg = metrics.registry
    sched = serve_mod._SlotScheduler(
        model, params, eos_id=None, default_sampling=GREEDY, seed_base=0,
        page=PAGE, arena_pages=None, prefix_cache=True,
        prefill_chunk_pages=1, metrics=metrics, tracer=tracer,
    )
    kinds = serve_mod.PASS_KINDS
    seconds = reg.counter("tpufw_serve_pass_seconds_total")
    steps = reg.counter("tpufw_serve_pass_steps_total")
    starved = reg.counter("tpufw_serve_pass_starved_seconds_total")
    phase_s = reg.counter("tpufw_serve_phase_seconds_total")
    ticks = reg.counter("tpufw_serve_ticks_total")
    chained = reg.counter("tpufw_serve_chunks_chained_total")

    def of(counter, kind, **labels):
        return counter.value(**{"pass": kind}, **labels)

    def starved_of(kind):
        return sum(of(starved, kind, phase=p) for p in serve_mod.SCHED_PHASES)

    # Before the first request: the new phase and every kind at 0.
    text = reg.render()
    assert 'tpufw_serve_phase_seconds_total{phase="serve_fetch"} 0' in text
    for kind in kinds:
        assert f'tpufw_serve_pass_seconds_total{{pass="{kind}"}} 0' in text
        assert f'tpufw_serve_pass_steps_total{{pass="{kind}"}} 0' in text
        assert (
            f'tpufw_serve_pass_starved_seconds_total{{pass="{kind}",'
            'phase="serve_fetch"} 0'
        ) in text
    _wait_idle(tracer)
    assert sum(of(seconds, k) + starved_of(k) for k in kinds) == 0.0

    def neighbours(first_a, first_b, new_a=161, new_b=9):
        """A (40 tokens, 10 chunks of 16 steps) streams; B (40 tokens: 3
        prefill chunks, 8 steps) is submitted at A's first token."""
        q = queue.Queue()
        sched.submit_stream([[first_a, 5, 9, 2, 6] * 8], new_a, None, q)
        assert q.get(timeout=300)[0] == "chunk"
        b = threading.Thread(
            target=sched.submit, args=([[first_b, 5, 9, 2, 6] * 8], new_b)
        )
        b.start()
        while q.get(timeout=300)[0] != "done":
            pass
        b.join(timeout=300)
        assert not b.is_alive()
        _wait_idle(tracer)

    neighbours(1, 2)  # builds the pool and every program
    before = {
        "rest": _resting_since(tracer), "ticks": ticks.value(),
        "wait": phase_s.value(phase="serve_wait"), "chained": chained.value(),
        **{k: (of(seconds, k), of(steps, k), starved_of(k)) for k in kinds},
    }
    n0 = len(tracer._events)
    neighbours(3, 4)
    rest = _resting_since(tracer)
    warm = [e for e in tracer._events[n0:] if e["ph"] == "X"]
    grown = {
        k: tuple(
            now - was for now, was in
            zip((of(seconds, k), of(steps, k), starved_of(k)), before[k])
        )
        for k in kinds
    }

    # One kind a pass, by what ran ahead of its decode chunk. A decodes
    # from its own final chunk to the end, so every pass after that one
    # has a decode chunk and "since the chunk before" is "in this pass".
    chunks = [e for e in warm if e["name"] == "serve_decode_chunk"]
    assert len(chunks) == ticks.value() - before["ticks"] >= 10
    ahead, behind_k, plain_k, seen_chunk = 0, 0, 0, False
    for e in warm:
        if e["name"] == "serve_prefill_chunk":
            ahead += 1 + e["args"]["final"]  # a final chunk brings its insert
        elif e["name"] == "serve_decode_chunk":
            if seen_chunk:
                assert e["args"]["ahead"] == ahead, e
            else:  # A's first: two passes without a chunk came before it
                assert e["args"]["ahead"] == 2 and ahead == 4
            assert (e["args"]["key_rung"], e["args"]["row_rung"]) == (
                256, sched.n_slots
            )
            behind_k += e["args"]["k"] * (ahead > 0)
            plain_k += e["args"]["k"] * (ahead == 0)
            ahead, seen_chunk = 0, True
    assert behind_k >= 4 * 16 and plain_k >= 16  # A's own, B's three
    # Where a boundary was quiet the successor went out before the read
    # (the chained order): such a chunk ran behind nothing, was enqueued
    # once like every other, and is counted; A's last chunks, B gone, are
    # such, and no chunk beside B's prefill is.
    links = [e for e in chunks if e["args"]["chained"]]
    assert len(links) == chained.value() - before["chained"] >= 2
    assert all(e["args"]["ahead"] == 0 for e in links)
    assert chunks[-1] in links and not chunks[0]["args"]["chained"]
    assert len(chunks) == sum(e["name"] == "serve_decode_dispatch" for e in warm)
    assert grown["decode_behind_prefill"][1] == behind_k
    assert grown["decode"][1] == plain_k
    assert grown["prefill_only"][1] == 0
    # Every kind ran, and starved seconds stay under the kind's own.
    for kind in kinds:
        spent, _, idle = grown[kind]
        assert 0.0 <= idle <= spent and spent > 0.0, (kind, grown[kind])
    # Passes and serve_wait tile the thread's time from one rest to the
    # next (the wait open at a scrape is booked whole when it closes):
    # what they miss is the loop's own lines between a pass's end and the
    # wait's span, never more than the time that went by.
    booked = sum(grown[k][0] for k in kinds) + (
        phase_s.value(phase="serve_wait") - before["wait"]
    )
    assert -0.05 <= booked - (rest - before["rest"]) <= 1e-6

    # The wait ends when the results are ready; the copy is the fetch.
    order = [e for e in warm if e["name"] in ("serve_device_wait", "serve_fetch")]
    assert [e["name"] for e in order] == ["serve_device_wait", "serve_fetch"] * (
        len(order) // 2
    )
    waited = [e["args"]["for"] for e in order[::2]]
    assert waited.count("prefill_final") == 2
    assert waited.count("decode") == len(chunks) == len(waited) - 2

    # One request, three legs, one rid.
    legs = {}
    for i, e in enumerate(warm):
        if e["name"] in ("req_queue", "req_prefill", "req_decode"):
            legs.setdefault(e["args"]["rid"], {})[e["name"]] = (i, e)
    assert sorted(legs) == [3, 4]
    for rid, new in ((3, 161), (4, 9)):
        assert set(legs[rid]) == {"req_queue", "req_prefill", "req_decode"}
        (i0, _), (i1, last) = legs[rid]["req_prefill"], legs[rid]["req_decode"]
        sat_in = [e for e in warm[i0:i1] if e["name"] == "serve_decode_chunk"]
        assert last["args"]["passes"] == len(sat_in)
        assert last["args"]["behind_prefill"] == sum(
            e["args"]["ahead"] > 0 for e in sat_in
        )
        assert last["args"]["tokens"] == new
        assert 0.0 < last["args"]["longest_pass_s"] <= last["dur"] / 1e6 + 1e-6
    assert legs[3]["req_decode"][1]["args"]["passes"] == 10
    assert legs[3]["req_decode"][1]["args"]["behind_prefill"] >= 4
    assert legs[4]["req_decode"][1]["args"]["passes"] == 1
    assert legs[4]["req_decode"][1]["args"]["behind_prefill"] == 1


# ------------------------------------- the live prefix of a row (PR 31)

def test_chunks_across_two_rungs_serve_the_monolithic_tokens(monkeypatch):
    """A 560-token prompt in 128-token chunks over a 1,024-slot row: the
    first four chunks read 512 key slots, the tail and the decode steps
    1,024 (tpufw.ops.kv_store's ladder, chosen inside the programs; its
    floor of 2,048 slots is put at 512 here, for a test's cost, and no
    other test builds a program over a 1,024-slot row). The
    served tokens are the monolithic scheduler's, and the scheduler's two
    counters say what the device read, by the rule the programs use."""
    from tpufw.infer.speculative import _pool_cursor
    from tpufw.ops import kv_store
    from tpufw.workloads import serve as serve_mod

    monkeypatch.setattr(kv_store, "MIN_RUNG", 512)

    base = LLAMA_CONFIGS["llama3_tiny"].decode_config()
    model = Llama(dataclasses.replace(base, max_seq_len=1024))
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompt = np.random.default_rng(11).integers(1, 200, 560).tolist()
    ref = _scheduler(model, params, 0).submit([prompt], 8)[0][0]

    metrics = serve_mod._Metrics()
    sched = serve_mod._SlotScheduler(
        model, params, eos_id=None, default_sampling=GREEDY, seed_base=0,
        page=PAGE, arena_pages=None, prefix_cache=True,
        prefill_chunk_pages=8, metrics=metrics,
    )
    read = metrics.registry.counter("tpufw_serve_attended_key_slots_total")
    whole = metrics.registry.counter("tpufw_serve_row_key_slots_total")
    assert read.value() == 0 and whole.value() == 0  # exposed at 0
    assert sched.submit([prompt], 8)[0][0] == ref

    n = sched.n_slots
    # Chunks end at 128, 256, 384, 512 (rung 512) and 560 (rung 1,024);
    # then ONE chunk of 8 decode steps of a pool of n rows: seven with
    # the one row live at 561..567 slots, and an eighth with no live row
    # at all, each at the least row rung (n / 8 = 1 row of n, gathered
    # whole: a row rung under the pool's width reads no key rung).
    assert kv_store.row_ladder(n) == (1, n)
    assert kv_store.attended_pair(model.cfg, n, 1, 561) == (1, 1024)
    assert read.value() == 4 * 512 + 1024 + 1 * 8 * 1024
    assert whole.value() == 5 * 1024 + n * 8 * 1024
    # The host's lengths are the device's cursors: the row's stands at
    # prompt + 8 steps (a done row's keeps counting), an empty slot's at 8.
    cursors = np.asarray(_pool_cursor(sched._pool.cache, n))
    assert sorted(cursors.tolist()) == [8] * (n - 1) + [568]
    text = metrics.registry.render()
    for name in ("attended_key_slots_total", "row_key_slots_total"):
        assert f"\ntpufw_serve_{name} " in text
