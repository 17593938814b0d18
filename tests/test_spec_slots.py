"""Speculative decoding as a slot-pool citizen (tpufw.infer.speculative
spec_steps / spec_draft_steps + acceptance-aware scheduling).

Contracts, all on CPU with the tiny model:

- PARITY: greedy verify is EXACT — whatever the proposer suggests
  (oracle accept-all, adversarial reject-all, n-gram self-draft), the
  emitted tokens are bit-equal to plain decode at the same precision;
  acceptance only changes how many passes it takes.
- SHAPE STABILITY: acceptance is DATA. After the first verify is
  traced, accept-all vs reject-all vs page churn add ZERO
  ``spec_verify`` traces (TRACE_COUNTS-pinned, like ``decode_steps``).
- DRAFT PAGES: a fused draft pool draws its pages from the SAME
  allocator as the target; releasing both rows returns every page —
  speculation cannot leak arena capacity.
- SCHEDULING: AcceptEMA starts optimistic, benches a cohort whose
  mean sinks below the waterline, re-probes every ``probe_every``
  fallback chunks, and stays benched when probing is disabled
  (draft-model pools).
- DISAGG: a spec-enabled DecodeEngine decodes a migrated cold bundle
  bit-equal to a plain replica, then returns every page on retire.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.infer import SamplingConfig, generate_text
from tpufw.infer import pages as pages_mod
from tpufw.infer import slots as slots_mod
from tpufw.infer import speculative as spec_mod
from tpufw.models import LLAMA_CONFIGS, Llama

GREEDY = SamplingConfig(temperature=0.0)
MAX_NEW = 9
PAGE = 16
N_SLOTS = 4
K = 3

# Greedy continuations with room between the top two logits at every
# step. The accept-all floor below needs the draft's t=1 program and the
# target's t=k+1 verify program to pick the SAME argmax; they are
# different XLA programs, a bf16 rounding apart, and a prompt whose
# continuation crosses a near-tie loses accepts to that rounding, not to
# the algorithm ([3] -> ... 137 and [2, 7] -> ... 134|86 sit at gaps of
# 0.012 and 0.004, under a bf16 ulp of the logit; jax 0.9.0's XLA:CPU
# lands on the other side of the first). Chosen by measuring the gaps
# over nine greedy steps: [90] keeps >= 0.32, [59] >= 0.21.
PROMPTS = [[1, 5, 9], [90], [59]]


@pytest.fixture(scope="module")
def tiny():
    base = LLAMA_CONFIGS["llama3_tiny"].decode_config()
    cfg = dataclasses.replace(base, max_seq_len=64)
    model = Llama(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    want = generate_text(
        model, params, PROMPTS, max_new_tokens=MAX_NEW, sampling=GREEDY
    )
    return cfg, model, params, want


def _paged_pool(cfg, row_model, params, kv_quant="", allocator=None,
                prefix_cache=True):
    pcfg = dataclasses.replace(
        cfg,
        kv_page=PAGE,
        kv_pages=2 * N_SLOTS * (cfg.max_seq_len // PAGE) + 1,
        kv_quant=kv_quant,
    )
    return pages_mod.PagedSlotPool.create_paged(
        Llama(pcfg), row_model, params, N_SLOTS, sampling=GREEDY,
        eos_id=None, allocator=allocator, prefix_cache=prefix_cache,
    )


def _admit_paged(pool, slot, prompt, i, budget=MAX_NEW - 1, extra=K):
    rng = jax.random.fold_in(jax.random.key(0), i)
    grant = pool.acquire_pages(prompt, len(prompt) + budget + extra)
    assert grant is not None
    ids, _shared = grant
    cache, _f, first, _d, seen = slots_mod.prefill_row(
        pool.row_model, pool.params, prompt, rng, sampling=GREEDY,
        eos_id=None, pad_to=len(prompt),
    )
    pool.insert_paged(
        slot, cache, first, len(prompt), budget, ids, 0, row_seen=seen
    )
    return first, ids


def _drive_spec(pool, proposer, first_tokens, max_new=MAX_NEW):
    """The scheduler's spec chunk loop, minus the scheduler: propose,
    one verify pass, extend each row by its accepted run."""
    rows = {i: [t] for i, t in enumerate(first_tokens)}
    passes = 0
    while any(len(t) < max_new for t in rows.values()):
        key = jax.random.fold_in(jax.random.key(1), passes)
        props = np.zeros((N_SLOTS, K), np.int32)
        for i in rows:
            props[i] = proposer(PROMPTS[i] + rows[i], K, i)
        out, n_emit, _accept = pool.spec_steps(props, key)
        out = np.asarray(out)
        n_emit = np.asarray(n_emit)
        for i in rows:
            take = min(int(n_emit[i]), max_new - len(rows[i]))
            rows[i].extend(out[i, :take].tolist())
        passes += 1
        assert passes < 40, "spec loop made no progress"
    return [rows[i] for i in range(len(PROMPTS))], passes


def _oracle(want):
    def prop(hist, k, i):
        n = len(hist) - len(PROMPTS[i])
        cont = list(want[i][n:n + k])
        return (cont + [0] * k)[:k]
    return prop


def _reject_all(want, vocab):
    oracle = _oracle(want)

    def prop(hist, k, i):
        return [(t + 1) % vocab for t in oracle(hist, k, i)]
    return prop


# ---------------------------------------------------------------- parity


def test_spec_accept_all_and_reject_all_bit_equal(tiny):
    cfg, model, params, want = tiny
    pool = slots_mod.SlotPool.create(
        model, params, N_SLOTS, sampling=GREEDY, eos_id=None
    )
    firsts = []
    for i, p in enumerate(PROMPTS):
        rng = jax.random.fold_in(jax.random.key(0), i)
        cache, _f, first, _d, seen = slots_mod.prefill_row(
            model, params, p, rng, sampling=GREEDY, eos_id=None,
            pad_to=32,
        )
        pool.insert(i, cache, first, len(p), MAX_NEW - 1, row_seen=seen)
        firsts.append(first)

    got, fast = _drive_spec(pool, _oracle(want), firsts)
    assert got == want

    # Reject-all must still be bit-equal — just slower (every pass
    # falls back to the verify's own argmax, 1 token/pass).
    pool2 = slots_mod.SlotPool.create(
        model, params, N_SLOTS, sampling=GREEDY, eos_id=None
    )
    firsts2 = []
    for i, p in enumerate(PROMPTS):
        rng = jax.random.fold_in(jax.random.key(0), i)
        cache, _f, first, _d, seen = slots_mod.prefill_row(
            model, params, p, rng, sampling=GREEDY, eos_id=None,
            pad_to=32,
        )
        pool2.insert(i, cache, first, len(p), MAX_NEW - 1, row_seen=seen)
        firsts2.append(first)
    got2, slow = _drive_spec(
        pool2, _reject_all(want, cfg.vocab_size), firsts2
    )
    assert got2 == want
    assert slow > fast


def test_spec_paged_parity_and_ngram(tiny):
    cfg, model, params, want = tiny
    pool = _paged_pool(cfg, model, params)
    firsts = [
        _admit_paged(pool, i, p, i)[0] for i, p in enumerate(PROMPTS)
    ]
    got, _ = _drive_spec(pool, _oracle(want), firsts)
    assert got == want

    # n-gram self-draft end to end: cold misses pad-fill and degrade
    # to 1 token/pass, never to a wrong emission.
    pool2 = _paged_pool(cfg, model, params)
    firsts2 = [
        _admit_paged(pool2, i, p, i)[0] for i, p in enumerate(PROMPTS)
    ]
    got2, _ = _drive_spec(
        pool2, lambda h, k, i: spec_mod.ngram_propose(h, k), firsts2
    )
    assert got2 == want


def test_spec_int8_bit_equal_to_int8_plain(tiny):
    cfg, model, params, _want = tiny
    # Reference = the int8 pool's own plain chunked decode (int8 is a
    # different precision from fp; spec must match ITS plain path).
    ref_pool = _paged_pool(cfg, model, params, kv_quant="int8")
    ref = {}
    for i, p in enumerate(PROMPTS):
        first, _ = _admit_paged(ref_pool, i, p, i)
        ref[i] = [first]
    ci = 0
    while any(len(t) < MAX_NEW for t in ref.values()):
        key = jax.random.fold_in(jax.random.key(1), ci)
        out = np.asarray(ref_pool.decode_steps(jax.random.split(key, 2)))
        for i in ref:
            take = min(2, MAX_NEW - len(ref[i]))
            ref[i].extend(out[i, :take].tolist())
        ci += 1
    want8 = [ref[i] for i in range(len(PROMPTS))]

    pool = _paged_pool(cfg, model, params, kv_quant="int8")
    firsts = [
        _admit_paged(pool, i, p, i)[0] for i, p in enumerate(PROMPTS)
    ]
    got, _ = _drive_spec(pool, _oracle(want8), firsts)
    assert got == want8


# ------------------------------------------------------- shape stability


def test_spec_zero_retrace_across_accept_and_churn(tiny):
    cfg, model, params, want = tiny
    pool = _paged_pool(cfg, model, params)
    firsts = [
        _admit_paged(pool, i, p, i)[0] for i, p in enumerate(PROMPTS)
    ]
    _drive_spec(pool, _oracle(want), firsts)  # warm: traces the verify

    before = dict(spec_mod.TRACE_COUNTS)
    # Page churn: release every row, re-admit at DIFFERENT prompt
    # lengths, then drive with the opposite acceptance extreme.
    for i in range(len(PROMPTS)):
        pool.release_slot(i)
    firsts2 = [
        _admit_paged(pool, i, p, i + 10)[0]
        for i, p in enumerate(PROMPTS)
    ]
    _drive_spec(pool, _reject_all(want, cfg.vocab_size), firsts2)
    assert spec_mod.TRACE_COUNTS["spec_verify"] == before["spec_verify"]


# ----------------------------------------------------------- draft pages


def test_draft_pool_pages_shared_allocator_no_leak(tiny):
    cfg, model, params, want = tiny
    tgt = _paged_pool(cfg, model, params)
    draft = _paged_pool(
        cfg, model, params, allocator=tgt.allocator, prefix_cache=False
    )
    rows = {}
    for i, p in enumerate(PROMPTS):
        first, _ = _admit_paged(tgt, i, p, i, extra=0)
        rows[i] = [first]
        # Draft admission charges the SAME allocator, with k extra
        # logical slots for the speculative overhang.
        _admit_paged(draft, i, p, i + 100, budget=MAX_NEW - 1 + K,
                     extra=0)
    passes = 0
    while any(len(t) < MAX_NEW for t in rows.values()):
        key = jax.random.fold_in(jax.random.key(1), passes)
        out, n_emit, accept = tgt.spec_draft_steps(draft, key, K)
        out = np.asarray(out)
        n_emit = np.asarray(n_emit)
        for i in rows:
            take = min(int(n_emit[i]), MAX_NEW - len(rows[i]))
            rows[i].extend(out[i, :take].tolist())
        passes += 1
        assert passes < 40
    # Same-model draft + greedy = accept-all: the fused path must hit
    # the ceil(max_new / (k+1)) floor, and stay bit-equal.
    assert [rows[i] for i in range(len(PROMPTS))] == want
    assert passes <= -(-MAX_NEW // (K + 1))

    assert tgt.allocator.in_use > 0
    for i in range(len(PROMPTS)):
        tgt.release_slot(i)
        draft.release_slot(i)
    assert tgt.allocator.in_use == 0


# ------------------------------------------------------------ scheduling


def test_accept_ema_units():
    ema = spec_mod.AcceptEMA(4, alpha=0.25, min_accept=0.25,
                             probe_every=3)
    # Optimistic start: an occupied slot speculates immediately.
    ema.occupy(0)
    assert ema.ema[0] == 1.0
    assert ema.use_spec([0])

    # Decay under total rejection: 1.0 -> 0.75 -> ... crosses 0.25
    # after five updates at frac=0.
    for n in range(5):
        assert ema.use_spec([0]), f"benched too early (update {n})"
        ema.update(0, 0.0)
    assert ema.ema[0] < 0.25
    assert ema.fallback_slots([0]) == 1
    assert not ema.use_spec([0])

    # Probe re-entry: every probe_every-th fallback chunk runs one
    # speculative pass anyway.
    assert not ema.use_spec([0])
    assert ema.use_spec([0])  # third consecutive fallback -> probe
    assert not ema.use_spec([0])  # counter reset

    # A good probe rehabilitates the slot (alpha pulls the EMA back
    # over the waterline).
    ema.update(0, 1.0)
    ema.update(0, 1.0)
    assert ema.use_spec([0])

    # Cohort mean decides: one hot slot can carry a cold joiner.
    ema.occupy(1)
    ema.update(1, 0.0)
    ema.update(1, 0.0)
    assert ema.use_spec([0, 1])

    # Vacated slots leave the cohort; an empty cohort never speculates.
    ema.vacate(0)
    ema.vacate(1)
    assert not ema.use_spec([0, 1])

    # probe_every=0 (draft-model pools): fallback is sticky — plain
    # chunks leave the draft KV stale, so probing would measure a
    # stale-context draft.
    sticky = spec_mod.AcceptEMA(1, alpha=0.25, min_accept=0.25,
                                probe_every=0)
    sticky.occupy(0)
    for _ in range(6):
        sticky.update(0, 0.0)
    assert all(not sticky.use_spec([0]) for _ in range(20))


# --------------------------------------------------------------- disagg


@pytest.mark.parametrize("kv_quant", ["", "int8"])
def test_disagg_spec_decode_parity_cold_bundle(tiny, kv_quant):
    from tpufw.serve.roles import DecodeEngine, PrefillEngine

    cfg, model, params, _want = tiny
    prompt = list(range(40, 72)) + [7, 9]
    new = 6

    def one(spec_k):
        # Fresh prefill replica per run: a trie hit under int8
        # recomputes the suffix over dequantized prefix KV, which is
        # approximate by design — cold bundles keep this a pure
        # spec-vs-plain comparison.
        pe = PrefillEngine(model, params, sampling=GREEDY, page=PAGE,
                           kv_quant=kv_quant, n_slots=2)
        de = DecodeEngine(model, params, sampling=GREEDY, page=PAGE,
                          kv_quant=kv_quant, n_slots=N_SLOTS, chunk=2,
                          spec_k=spec_k)
        toks = de.collect(de.submit(pe.prefill(prompt, new)))
        return toks, de

    plain, _ = one(0)
    spec, de = one(4)
    assert spec == plain
    assert de.spec_passes > 0
    assert de.pool.allocator.in_use == 0


def test_scheduler_spec_parity_vs_plain(tiny):
    from tpufw.workloads.serve import _Metrics, _SlotScheduler

    cfg, model, params, _want = tiny
    # Self-similar prompt so the n-gram draft gets real acceptance on
    # at least some passes; greedy verify keeps the output exact
    # either way.
    prompt = [5, 9, 5, 9, 5, 9, 5, 9, 5, 9]

    def run(spec_k):
        sched = _SlotScheduler(
            model, params, eos_id=None, default_sampling=GREEDY,
            metrics=_Metrics(), seed_base=0, page=PAGE,
            spec_k=spec_k, spec_draft="", spec_min_accept=0.25,
        )
        outs, _bw = sched.submit([prompt], 12, None)
        return outs[0]

    assert run(4) == run(0)
