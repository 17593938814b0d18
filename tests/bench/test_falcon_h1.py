"""The falcon_h1 family at the rehearsal widths on the CPU: its plain
reference against the program (full forward, scanned and unrolled;
prefill then decode through the cache; and through the serving pools:
whole-prompt prefill, left-padded contiguous prefill, prefill in chunks
with a padded tail, decode after each, a reused slot), what the program
declines for a model with per-slot state, the host's count of the state
it moves, the int8 weights and a lost carry told apart, the cost
functions' goldens and the configuration file's keys.

Tolerance ``F32_TOL``: program and reference both in float32 at highest
matmul precision over two layers differ by the order of their sums only
(the chunkwise recurrence in blocks of 16 against the token-by-token one
included); logits have unit scale. A state kept in bfloat16 or int8
weights are 50 to 1,000 times that."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs, harness
from benchmarks.costs import falcon_h1 as cost
from benchmarks.reference import common
from benchmarks.weights import make_weights

FAMILY = "falcon_h1"
CELL = "falconh1-instruct-burst"
CONFIG = "benchmarks/configs/falcon-h1-34b-6l.json"
F32_TOL = 2e-4
PAGE = 16
BLOCK = 16  # the rehearsal widths' mamba_chunk_size


def build(seed=3, positions=256):
    keys = harness.model_keys(harness.load_json(f"benchmarks/configs/rehearse/{FAMILY}.json"))
    keys["max_position_embeddings"] = positions
    ref, adapter = harness.family_modules(FAMILY)
    weights = make_weights(ref.weight_specs(keys), seed)
    cls, pc = adapter.program_model(keys, {})
    pc32 = dataclasses.replace(pc, dtype=jnp.float32)
    return keys, ref, adapter.to_program(weights, keys), weights, cls, pc32


@pytest.fixture(scope="module")
def built():
    return build()


def scanned(built):
    """The same model with its trunk under ``nn.scan``: the parameters of
    the layers stacked, so the STATE and PAGE leaves of every layer are
    stacked ``[L, B, ...]`` in the pools too."""
    keys, ref, params, weights, cls, pc32 = built
    stacked = {k: v for k, v in params.items() if not k.startswith("layer_")}
    stacked["layers"] = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *[params[f"layer_{i}"] for i in range(keys["num_hidden_layers"])])
    return keys, ref, stacked, weights, cls, dataclasses.replace(pc32, scan_layers=True)


def tokens_of(n, keys, seed):
    return jax.random.randint(jax.random.key(seed), (n,), 1, keys["vocab_size"])


# ------------------------------------------- reference against program


@pytest.mark.parametrize("trunk", ["unrolled", "scanned"])
def test_full_forward_agrees(built, trunk):
    keys, ref, params, weights, cls, pc32 = built if trunk == "unrolled" else scanned(built)
    tokens = tokens_of(96, keys, 1)
    want, margin = ref.logits(weights, keys, tokens, jnp.arange(96))
    with jax.default_matmul_precision("highest"):
        got = cls(pc32).apply({"params": params}, tokens[None])[0]
    assert 0.5 < float(jnp.std(want)) < 2.0, "seeded weights give logits of unit scale AFTER lm_head_multiplier"
    assert margin.shape == (96,) and bool(jnp.all(jnp.isinf(margin))), "a dense model routes nothing"
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


@pytest.mark.parametrize("n_prompt", [BLOCK - 3, BLOCK, BLOCK + 5, 2 * BLOCK, 40])
def test_prefill_then_decode_through_the_cache_agrees(built, n_prompt):
    """Prompts that end inside, at and past a block of the chunkwise
    recurrence; the decode steps then continue from its state, the
    convolution's tail and the keys."""
    keys, ref, params, weights, cls, pc32 = built
    n_new = 12
    tokens = tokens_of(n_prompt + n_new, keys, 2)
    want, _ = ref.logits(weights, keys, tokens, jnp.arange(n_prompt + n_new))
    model = cls(pc32.decode_config())

    def apply(cache, toks, pos):
        out, new = model.apply(
            {"params": params, **cache}, toks, positions=pos,
            segment_ids=jnp.ones_like(toks), mutable=["cache"],
        )
        return out, {"cache": new["cache"]}

    with jax.default_matmul_precision("highest"):
        logits, cache = apply({}, tokens[None, :n_prompt], jnp.arange(n_prompt)[None])
        worst = float(jnp.max(jnp.abs(logits[0] - want[:n_prompt])))
        for i in range(n_prompt, n_prompt + n_new):
            logits, cache = apply(cache, tokens[None, i: i + 1], jnp.array([[i]]))
            worst = max(worst, float(jnp.max(jnp.abs(logits[0, 0] - want[i]))))
    assert worst < F32_TOL


def test_int8_weights_are_told_apart(built):
    keys, ref, params, weights, cls, pc32 = built
    tokens = tokens_of(64, keys, 4)
    want, _ = ref.logits(weights, keys, tokens, jnp.arange(64))
    rounded = {
        k: v if any(s in k for s in ref.INT8_KEEP) else common.int8_round_trip(v, v.ndim - 2)
        for k, v in weights.items()
    }
    _, adapter = harness.family_modules(FAMILY)
    with jax.default_matmul_precision("highest"):
        got = cls(pc32).apply({"params": adapter.to_program(rounded, keys)}, tokens[None])[0]
    assert float(jnp.max(jnp.abs(got - want))) > 50 * F32_TOL


def test_a_bfloat16_state_is_told_apart(built, monkeypatch):
    """The recurrent state kept in bfloat16 between calls, where the
    configuration states float32: 40 decode steps through the cache leave
    the reference by far more than the tolerance."""
    from tpufw.models import falcon_h1

    keys, ref, params, weights, cls, pc32 = built
    monkeypatch.setattr(falcon_h1, "SSM_STATE_DTYPE", jnp.bfloat16)
    tokens = tokens_of(24 + 40, keys, 5)
    want, _ = ref.logits(weights, keys, tokens, jnp.arange(64))
    model = cls(pc32.decode_config())
    with jax.default_matmul_precision("highest"):
        _, new = model.apply({"params": params}, tokens[None, :24], positions=jnp.arange(24)[None],
                             segment_ids=jnp.ones((1, 24), jnp.int32), mutable=["cache"])
        for i in range(24, 64):
            out, new = model.apply({"params": params, "cache": new["cache"]}, tokens[None, i:i + 1],
                                   positions=jnp.array([[i]]), segment_ids=jnp.ones((1, 1), jnp.int32), mutable=["cache"])
    assert float(jnp.max(jnp.abs(out[0, 0] - want[63]))) > 10 * F32_TOL


def test_attention_decays_and_gates_are_not_degenerate(built):
    """Seeded weights: with each multiplied matrix drawn for its
    multiplier, softmax is neither uniform nor one-hot, the decays a_t
    span forgetting in a few tokens to remembering hundreds, and the
    gates' pre-activations have unit scale, so a wrong cache moves the
    logits. With plain fan-in scaling the keys x 0.011 flatten softmax."""
    keys, ref, _, weights, _, _ = built
    t = 128
    x = jax.random.normal(jax.random.key(0), (t, keys["hidden_size"]))
    u = common.rms_norm(x, jnp.ones(keys["hidden_size"]), 1e-6)
    p = "layers.1."
    h, hk, hd = keys["num_attention_heads"], keys["num_key_value_heads"], keys["head_dim"]
    assert h // hk == 5, "five query heads a K/V head, as published"

    def last_row_max(w):
        q = ref.rope_half(common.mm(u, w[p + "q_proj"]).reshape(t, h, hd), jnp.arange(t), 1e11)
        k = ref.rope_half((common.mm(u, w[p + "k_proj"]) * keys["key_multiplier"]).reshape(t, hk, hd), jnp.arange(t), 1e11)
        scores = jnp.einsum("hd,khd->hk", q[-1], jnp.repeat(k, h // hk, axis=1)) * hd ** -0.5
        return jnp.max(jax.nn.softmax(scores, axis=-1), axis=-1)  # [heads]

    top = last_row_max(weights)
    assert 2.0 / t < float(jnp.median(top)) < 0.9, "neither uniform over 128 keys nor one-hot"
    plain = {**weights, p + "k_proj": (weights[p + "k_proj"].astype(jnp.float32) * keys["key_multiplier"]).astype(jnp.bfloat16)}
    assert float(jnp.max(last_row_max(plain))) < 1.2 / t, "fan-in-scaled keys x 0.011: softmax flat to within 20%"
    z, xs, b_in, c_in, delta, a_rate = ref.ssm_inputs(weights, p, keys, u * keys["ssm_in_multiplier"])
    decay = jnp.exp(-delta * a_rate)
    assert bool(jnp.all((decay > 0) & (decay <= 1)))
    assert float(jnp.min(decay)) < 0.6 and float(jnp.max(decay)) > 0.995 and 0.8 < float(jnp.median(decay)) < 0.995
    assert 0.5 < float(jnp.std(z)) < 2.0 and 0.2 < float(jnp.std(xs)) < 2.0
    gate = common.mm(common.rms_norm(x, jnp.ones(keys["hidden_size"]), 1e-6), weights[p + "mlp.gate"]) * keys["mlp_multipliers"][0]
    assert 0.5 < float(jnp.std(gate)) < 2.0


def test_state_stays_finite_over_8192_positions(built):
    """The seeded decays (Mamba-2's initial ranges, ``decay_leaves``) over
    four times the longest context the cell admits: the reference's
    token-by-token recurrence neither overflows nor dies."""
    keys, ref, _, weights, _, _ = built
    x = jax.random.normal(jax.random.key(2), (8192, keys["hidden_size"]))
    u = common.rms_norm(x, jnp.ones(keys["hidden_size"]), 1e-6) * keys["ssm_in_multiplier"]
    with jax.default_matmul_precision("highest"):
        _, xs, b_in, c_in, delta, a_rate = ref.ssm_inputs(weights, "layers.0.", keys, u)
        y = jax.jit(ref.recurrence)(xs, b_in, c_in, delta, a_rate, jnp.ones_like(a_rate))
    assert bool(jnp.all(jnp.isfinite(y))) and 1e-2 < float(jnp.std(y[-512:])) < 1e2


def test_decay_leaves_are_mamba2s_initial_ranges():
    ref, _ = harness.family_modules(FAMILY)
    z = jax.random.normal(jax.random.key(0), (4096,)).astype(jnp.bfloat16)
    a_log, dt_bias = ref.decay_leaves(z, z)
    a, dt = jnp.exp(a_log), jax.nn.softplus(dt_bias)
    assert a_log.dtype == dt_bias.dtype == jnp.float32
    assert 1.0 <= float(a.min()) < 1.2 and 15.8 < float(a.max()) <= 16.0
    assert 1e-3 <= float(dt.min()) * 1.0001 < 1.2e-3 and 0.09 < float(dt.max()) <= 0.1 * 1.0001


# ------------------------------------------------ through the serving pools


def pool_of(built, n_slots=3, positions=256, prefix_cache=True):
    from tpufw.infer import SamplingConfig
    from tpufw.infer import pages

    keys, ref, params, weights, cls, pc32 = built
    cfg = dataclasses.replace(pc32.decode_config(), max_seq_len=positions)
    paged = dataclasses.replace(cfg, kv_page=PAGE, kv_pages=n_slots * (positions // PAGE) + 1)
    return pages.PagedSlotPool.create_paged(
        cls(paged), cls(cfg), params, n_slots,
        sampling=SamplingConfig(temperature=0.0), eos_id=None, prefix_cache=prefix_cache,
    )


def peek(pool):
    """Next-token logits [slots, V] out of the pool's own cache: what its
    decode step computes before it samples (nothing is donated)."""
    from tpufw.infer.generate import _model_apply

    @jax.jit
    def f(params, cache, token, pos):
        apply = _model_apply(pool.model, params)
        return apply(cache, token[:, None], pos[:, None], jnp.ones((token.shape[0], 1), jnp.int32))[0][:, -1]

    with jax.default_matmul_precision("highest"):
        return f(pool.params, pool.cache, pool.token, pool.pos)


def admit_whole(pool, slot, prompt, budget, pad_to):
    from tpufw.infer import slots

    ids, shared = pool.acquire_pages(prompt, len(prompt) + budget)
    assert shared == 0
    with jax.default_matmul_precision("highest"):
        cache, _, first, _, seen = slots.prefill_row(
            pool.row_model, pool.params, prompt, jax.random.key(0),
            sampling=pool.sampling, eos_id=None, pad_to=pad_to,
        )
        pool.insert_paged(slot, cache, first, len(prompt), budget, ids, 0, row_seen=seen)
    return cache, first


def admit_chunked(pool, slot, prompt, budget, chunk_pages):
    with jax.default_matmul_precision("highest"):
        cp = pool.start_chunked(prompt, len(prompt) + budget, jax.random.key(0), chunk_pages)
        while pool.chunk_step(cp) != "done":
            pass
        pool.finalize_chunked(slot, cp, budget)
    return cp


def decode(pool, n):
    with jax.default_matmul_precision("highest"):
        return np.asarray(pool.decode_steps(jax.random.split(jax.random.key(1), n)))


def check_row(built, pool, slot, prompt, first, n_steps=6):
    """The pool's logits for ``slot`` agree with the reference's after the
    prompt and again after ``n_steps`` decode steps through the pool."""
    keys, ref, _, weights, _, _ = built

    def reference(seq, at):
        # Past one attention block the reference wants whole blocks:
        # zeros after the real tokens, which causality keeps out.
        pad = -len(seq) % common.QUERY_BLOCK if len(seq) > common.QUERY_BLOCK else 0
        return ref.logits(weights, keys, jnp.asarray(seq + [0] * pad), jnp.asarray(at))[0]

    seq = list(prompt) + [first]
    want = reference(seq, [len(prompt) - 1, len(prompt)])
    assert int(jnp.argmax(want[0])) == first, "the prefill sampled the reference's first token"
    assert float(jnp.max(jnp.abs(peek(pool)[slot] - want[1]))) < F32_TOL
    out = decode(pool, n_steps)[slot]
    seq = seq + out.tolist()
    want = reference(seq, [len(seq) - 1])
    assert float(jnp.max(jnp.abs(peek(pool)[slot] - want[0]))) < F32_TOL


@pytest.mark.parametrize("path,n_prompt,chunk_pages", [
    ("whole", 40, 0),        # paged rows prefill at their exact width
    ("contiguous", 40, 0),   # padded on the LEFT to its bucket of 64
    ("chunked", 40, 1),      # chunks of 16, 16 and 8 padded to 16
    ("chunked", 40, 2),      # 32 and a padded 8
    ("chunked", 75, 2),      # 32, 32 and 11 padded to 16: the carry crosses two boundaries
    ("chunked", 96, 3),      # two whole chunks of 48, each three blocks of the recurrence
    ("chunked_scanned", 75, 2),  # STATE and PAGE leaves of every layer stacked [L, B, ...] under nn.scan
])
def test_prefill_through_the_pools_then_decode_agrees(built, path, n_prompt, chunk_pages):
    """All three ways a prompt reaches a slot: the state, the
    convolution's tail and the keys ride in the row twin from chunk to
    chunk, do not move on padding (left: the row's empty past; right: a
    padded tail), and arrive whole at the insert."""
    from tpufw.infer import SamplingConfig
    from tpufw.infer import slots

    if path == "chunked_scanned":
        built = scanned(built)
    keys, _, params, _, cls, pc32 = built
    prompt = tokens_of(n_prompt, keys, 12).tolist()
    if path == "contiguous":
        model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=128))
        pool = slots.SlotPool.create(model, params, 3, sampling=SamplingConfig(temperature=0.0))
        assert pool.state_bytes > 0
        with jax.default_matmul_precision("highest"):
            cache, _, first, _, seen = slots.prefill_row(
                model, params, prompt, jax.random.key(0), sampling=pool.sampling, eos_id=None, pad_to=64)
            pool.insert(2, cache, first, len(prompt), 16, row_seen=seen)
        check_row(built, pool, 2, prompt, first)
        return
    pool = pool_of(built)
    if path == "whole":
        _, first = admit_whole(pool, 1, prompt, 16, n_prompt)
        check_row(built, pool, 1, prompt, first)
        return
    cp = admit_chunked(pool, 2, prompt, 16, chunk_pages)
    assert cp.n_chunks == -(-n_prompt // (chunk_pages * PAGE))
    if path == "chunked_scanned":
        state = pool.cache["cache"]["layers"]["ssm"]["ssm_state"]
        keys_leaf = pool.cache["cache"]["layers"]["attn"]["cached_key"]
        assert state.shape == (2, 3, 4, 16, 32) and keys_leaf.shape[:1] == (2,), "both kinds, stacked by layer"
    check_row(built, pool, 2, prompt, cp.first_int)


def per_slot_leaves(tree):
    from tpufw.ops import kv_store

    return {
        jax.tree_util.keystr(p): leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if kv_store.path_role(p).kind == kv_store.STATE
    }


def test_a_reused_slot_holds_nothing_of_the_longer_row_before_it(built):
    """State AND keys: the slot's per-slot leaves are the new row's to the
    bit, and its logits (which read the slot's pages) are the reference's
    for the new row alone."""
    keys = built[0]
    pool = pool_of(built, n_slots=2)
    long_prompt = tokens_of(120, keys, 13).tolist()
    admit_chunked(pool, 0, long_prompt, 24, 2)
    decode(pool, 8)
    before = {k: np.asarray(v) for k, v in per_slot_leaves(pool.cache).items()}
    assert len(before) == 4 and all(np.abs(v[0]).max() > 0 for v in before.values()), "two layers x (ssm_state, conv_state)"
    assert pool.state_bytes == sum(v.nbytes for v in before.values())
    pool.release_slot(0)
    short = tokens_of(20, keys, 14).tolist()
    row_cache, first = admit_whole(pool, 0, short, 16, 20)
    row = per_slot_leaves(row_cache)
    for path, leaf in per_slot_leaves(pool.cache).items():
        assert bool(jnp.all(leaf[0] == row[path][0])), path  # the new row's, to the bit
    check_row(built, pool, 0, short, first)


# ---------------------------------------------------------- the declines


def test_shared_pages_are_not_attached_and_the_decline_is_named(built):
    """Two prompts with a common first 64 tokens: the second gets no page
    of the first (its state would start from zero), the pool says why,
    and the second row gives the reference's logits."""
    keys = built[0]
    common_part = tokens_of(64, keys, 21).tolist()
    a = common_part + tokens_of(16, keys, 22).tolist()
    b = common_part + tokens_of(32, keys, 23).tolist()
    pool = pool_of(built, n_slots=2, prefix_cache=True)
    assert pool.prefix is None and pool.prefix_decline == "state_layers"
    cpa = admit_chunked(pool, 0, a, 8, 2)
    cpb = admit_chunked(pool, 1, b, 8, 2)
    assert cpa.shared_n == cpb.shared_n == 0 and pool.prefix_hits == 0
    assert not set(cpa.page_ids) & set(cpb.page_ids)
    check_row(built, pool, 1, b, cpb.first_int, n_steps=4)


def test_the_scheduler_counts_the_declines_and_the_state_it_moves(built):
    """The host's count against the program's shapes: a dispatched decode
    step reads and writes the state of EVERY slot (the pool's
    ``[slots, ...]`` leaves whole), a prefill chunk and an insert one
    row's; the live share is the rows that still deliver a token."""
    from tpufw.infer import SamplingConfig
    from tpufw.workloads import serve

    keys, ref, params, weights, cls, pc32 = built
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=256))
    metrics = serve._Metrics()
    sched = serve._SlotScheduler(
        model, params, eos_id=None, default_sampling=SamplingConfig(temperature=0.0),
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=True,
        prefill_chunk_pages=2, metrics=metrics,
    )
    shared = tokens_of(64, keys, 31).tolist()
    with jax.default_matmul_precision("highest"):
        outs = [sched.submit([shared + tokens_of(8, keys, 32 + i).tolist()], 5)[0][0] for i in range(2)]
    reg = metrics.registry
    value = lambda name: reg.counter("tpufw_serve_" + name).value()
    assert reg.counter("tpufw_serve_prefix_declined_total").value(reason="state_layers") == 2
    assert value("prefix_hits_total") == value("prefix_misses_total") == 0
    pool = sched._pool
    # The program's shapes: what one slot keeps, over the two layers.
    state = pool.cache["cache"]["layer_0"]["ssm"]["ssm_state"]
    tail = pool.cache["cache"]["layer_1"]["ssm"]["conv_state"]
    assert state.shape == (sched.n_slots, 4, 16, 32) and state.dtype == jnp.float32
    assert tail.shape == (sched.n_slots, 3, 64 + 2 * 2 * 32)
    a_slot = 2 * (4 * 16 * 32 * 4 + 3 * 192 * tail.dtype.itemsize)
    assert reg.gauge("tpufw_serve_state_bytes").value() == pool.state_bytes == sched.n_slots * a_slot
    assert reg.gauge("tpufw_serve_state_slots").value() == sched.n_slots
    # A prompt of 72 tokens is chunks of 32, 32 and 8 padded to a page; the rest are steps x slots.
    row = pool.cache_len
    chunks, inserts = 2 * 3, 2
    slot_steps = value("row_key_slots_total") / row - chunks
    assert slot_steps > 0 and slot_steps % sched.n_slots == 0
    assert value("state_moved_bytes_total") == (chunks + inserts + slot_steps) * 2 * a_slot
    # Each answer of 5 tokens: the first from the prefill, four from decode steps that were live.
    assert value("state_live_bytes_total") == (chunks + inserts + 2 * 4) * 2 * a_slot
    text = reg.render()
    assert 'tpufw_serve_prefix_declined_total{reason="state_layers"} 2' in text
    assert "tpufw_serve_state_moved_bytes_total" in text and "tpufw_serve_state_live_bytes_total" in text
    # The second answer is the reference's greedy continuation of ITS prompt.
    prompt = shared + tokens_of(8, keys, 33).tolist()
    seq = prompt + outs[1]
    want, _ = ref.logits(weights, keys, jnp.asarray(seq[:-1]), jnp.arange(len(prompt) - 1, len(seq) - 1))
    served = want[jnp.arange(5), jnp.asarray(outs[1])]
    assert float(jnp.max(jnp.max(want, axis=-1) - served)) < 1e-3


def test_a_model_without_state_has_no_state_counters():
    """Always on where the pool holds STATE, absent elsewhere: a Llama
    scheduler's /metrics gain no series."""
    from tpufw.infer import SamplingConfig
    from tpufw.models import LLAMA_CONFIGS, Llama
    from tpufw.workloads import serve

    cfg = dataclasses.replace(LLAMA_CONFIGS["llama3_tiny"].decode_config(), max_seq_len=64)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    metrics = serve._Metrics()
    sched = serve._SlotScheduler(
        model, params, eos_id=None, default_sampling=SamplingConfig(temperature=0.0),
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=True, prefill_chunk_pages=1, metrics=metrics,
    )
    sched.submit([list(range(1, 25))], 4)
    text = metrics.registry.render()
    assert "state_moved_bytes_total" not in text and "state_live_bytes_total" not in text
    assert metrics.registry.gauge("tpufw_serve_state_bytes").value() == 0


def test_export_splice_and_speculation_refuse_the_family_by_name(built):
    from tpufw.infer import SamplingConfig
    from tpufw.workloads import serve

    keys = built[0]
    pool = pool_of(built, n_slots=2)
    cp = admit_chunked(pool, 0, tokens_of(24, keys, 41).tolist(), 8, 1)
    with pytest.raises(ValueError, match=r"export_slot: FalconH1 keeps per-slot state"):
        pool.export_slot(0)
    with pytest.raises(ValueError, match=r"splice_slot: FalconH1 keeps per-slot state"):
        pool.splice_slot(1, {}, cp.page_ids)
    with pytest.raises(ValueError, match=r"speculative decoding: FalconH1"):
        pool.spec_steps(np.zeros((2, 2), np.int32), jax.random.key(0))
    _, _, params, _, cls, pc32 = built
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=256))
    sched = serve._SlotScheduler(
        model, params, eos_id=None, default_sampling=SamplingConfig(temperature=0.0),
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=True, prefill_chunk_pages=1, spec_k=2,
    )
    with pytest.raises(ValueError, match=r"TPUFW_SERVE_SPEC_K=2: FalconH1 keeps per-slot state"):
        sched.submit([tokens_of(24, keys, 42).tolist()], 4)


# ------------------------------------------------- costs and configuration


def real_keys():
    return harness.model_keys(harness.load_json(CONFIG))


def test_cost_goldens():
    """ISSUE 37's table, redone by the cost functions."""
    c = real_keys()
    p = cost.layer_params(c)
    assert (p["attn"], p["ssm"], p["mlp"]) == (31_457_280, 68_351_072, 330_301_440)
    assert cost.layer_total(c) == 430_120_032 and p["embed"] + p["head"] == 2_673_868_800
    memory = harness.load_json(CONFIG)["memory"]
    assert cost.parameters(c) == 5_254_594_112 == memory["parameters"]
    assert cost.parameters({**c, "num_hidden_layers": 72}) == 33_642_516_224
    assert cost.active_matmul_params(c) == 6 * 430_109_792 + 1_336_934_400
    assert cost.cache_bytes_per_token(c) == costs.cache_bytes_per_token(FAMILY, c) == 12_288 == memory["cache_bytes_per_token"]
    assert cost.state_bytes_per_row(c) == 25_350_144 == memory["state_bytes_per_slot"]
    assert costs.decode_step_bytes(FAMILY, c, 0, []) == 7_835_319_424.0
    assert costs.decode_step_bytes(FAMILY, c, 10, [512] * 10) == 8_405_339_264.0
    assert cost.ssd_chunk_flops(c, 512) == 14_710_996_992.0
    assert cost.ssd_chunk_bytes(c, 512) == 107_151_360.0
    assert cost.ssd_step_bytes(c, 32) == 1_614_163_968.0
    assert costs.prefill_flops(FAMILY, c, [512]) == pytest.approx(2.66804822016e12)
    assert costs.prefill_chunk_flops(FAMILY, c, 512, [512]) == pytest.approx(2.66804822016e12 - 2.0 * 1_336_934_400)


def test_state_counts_by_the_row_and_keys_by_the_token():
    c = real_keys()
    one, long = cost.decode_step_bytes(c, [100]), cost.decode_step_bytes(c, [1500])
    assert long - one == 1400 * 12_288, "K/V grow by the token, in every layer"
    none = cost.decode_step_bytes(c, [])
    assert one - none == 2 * 5120 + 100 * 12_288 + 2 * 25_350_144, "a row's embedding, its keys, its state read and written"
    # The head is a third of a step's weight bytes here and a twenty-fourth in the 72-layer model.
    head = 2 * cost.layer_params(c)["head"]
    assert 0.335 < head / none < 0.345
    assert 0.040 < head / cost.decode_step_bytes({**c, "num_hidden_layers": 72}, []) < 0.042
    # Causal pairs in every layer; the recurrence by the token.
    p = cost.prefill_flops(c, [1024]) - 2 * cost.prefill_flops(c, [512])
    assert p == pytest.approx(2.0 * 20 * 256 * 6 * (1024 * 1025 / 2 - 2 * 512 * 513 / 2) - 2.0 * 1_336_934_400)
    assert cost.ssd_chunk_flops(c, 1024) == 2 * cost.ssd_chunk_flops(c, 512)
    assert cost.ssd_step_bytes(c, 32) / 32 == pytest.approx(2 * 6 * 32 * 128 * 256 * 4 + 6 * (2 * 4096 + 2 * 512 + 32) * 2)


def test_catalog_keys_kept_or_listed_as_reduced():
    # The catalog's row as ISSUE 37 drew it, kept beside this file: a test
    # reads nothing outside its checkout.
    with open(os.path.join(os.path.dirname(__file__), "falcon_h1_catalog_row.json")) as f:
        row = json.load(f)
    assert row["name"] == "Falcon-H1-34B-Instruct"
    config = harness.load_json(CONFIG)
    entry = harness.config_entry(harness.load_benchmark(), "falcon-h1-34b-6l")
    assert config["source"] == row["source_url"] == entry["source"] and len(entry["source"]) <= 200
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == ["max_position_embeddings", "num_hidden_layers"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value and "->" in config["reduced"][key]
        else:
            assert config[key] == value, key
    assert config["vocab_size"] == 261_120 and config["num_hidden_layers"] == 6
    assert config["memory"]["weights_bytes_bf16"] == 2 * config["memory"]["parameters"]
    for k in ("w_in_split", "norm_after_gate", "layers", "decay", "weights", "dtype", "rotary"):
        assert k in config["assumed"]
    assert "twelve pipeline stages" in config["deployment"]


def test_the_reference_stands_alone_and_covers_every_answer():
    ref, _ = harness.family_modules(FAMILY)
    with open(ref.__file__) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+tpufw", src, re.M), "the reference imports nothing of the program"
    bench = harness.load_benchmark()
    cells = [w for w in bench["workloads"] if harness.load_json(harness.config_entry(bench, w["config"])["file"])["family"] == FAMILY]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    for w in cells:
        config = harness.load_json(harness.config_entry(bench, w["config"])["file"])
        mix = harness.load_json(harness.traffic_path(w["traffic"]))
        assert mix["output"]["cap"] <= ref.MAX_AT and mix["rehearse"]["output"]["cap"] <= ref.MAX_AT
        assert mix["prompt"]["cap"] + mix["output"]["cap"] <= config["max_position_embeddings"]
        assert config["vocab_size"] % ref.HEAD_BLOCK == 0, "the head in whole blocks"
    assert harness.missing_parts(bench, cells[0], harness.load_json(CONFIG)) == []


def test_the_mix_is_issue_37s_and_its_traced_stretch_holds_prefill_and_decode():
    from benchmarks import traffic
    from benchmarks.runners import serve as runner

    mix = harness.load_json(harness.traffic_path("instruct-burst"))
    assert mix["prompt"] == {**mix["prompt"], "base": 64, "alpha": 1.0, "cap": 1024, "quantum": 64}
    assert mix["output"] == {**mix["output"], "base": 64, "alpha": 1.0, "cap": 512}
    arr = mix["arrivals"]
    assert (arr["process"], arr["burst_factor"], arr["dwell_s"]) == ("mmpp", 4, 5)
    assert (mix["ramp_s"], mix["drain_s"], mix["shape_seed"]) == (10, 20, 0)
    assert mix["server_env"] == {"TPUFW_SERVE_SLOTS": 32, "TPUFW_SERVE_PAGE": 16, "TPUFW_SERVE_PREFILL_CHUNK": 32,
                                 "TPUFW_SERVE_CHUNK": 8, "TPUFW_SERVE_CACHE_FLOOR": 2048}
    assert arr["rate_rps"] == pytest.approx(0.8 * mix["knee"]["knee_rps"], rel=0.02)
    reqs = traffic.schedule(mix, 1, 45.0, 261_120)
    in_win = [r for r in reqs if r.t >= 0]
    assert len(in_win) == round(arr["rate_rps"] * 45) or abs(len(in_win) - arr["rate_rps"] * 45) <= 2
    lens = sorted(len(r.prompt) for r in in_win)
    assert lens[0] == 64 and lens[-1] == 1024 and lens[len(lens) // 2] in (128, 192)
    # The traced 6 s start at an arrival and hold further arrivals (their
    # chunks) beside the decode steps of the rows admitted before them.
    offset, anchor = runner.trace_offset(reqs, 45.0)
    assert anchor is not None
    inside = [r for r in reqs if offset <= r.t <= offset + runner.TRACE_SECONDS]
    before = [r for r in reqs if offset - 10.0 <= r.t < offset and r.max_new >= 64]
    assert len(inside) >= 3 and sum(len(r.prompt) for r in inside) >= 1024 and before


def test_new_readers_report_nothing_where_there_is_nothing_to_read():
    from benchmarks.metrics import state_live_share

    obs = {"prom0": {}, "prom1": {}, "rehearse": False, "trace": None, "device": {"kind": "TPU v5 lite"}}
    assert state_live_share.read(obs) is None, "a program without the counters (the parent)"
    moved, live = "tpufw_serve_state_moved_bytes_total", "tpufw_serve_state_live_bytes_total"
    obs["prom0"].update({moved: 0.0, live: 0.0})
    obs["prom1"].update({moved: 0.0, live: 0.0})
    assert state_live_share.read(obs) is None, "nothing dispatched between the scrapes"
    obs["prom1"].update({moved: 32 * 8 * 50_700_288.0, live: 10 * 8 * 50_700_288.0})
    assert state_live_share.read(obs) == pytest.approx(31.25)
    per_layer = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    # The two readers that divide by a chunk's width do NOT list the cell:
    # the width is read off shapes ``[1, width, hidden]`` and this family's
    # convolution is as wide as its hidden size (4096 + 2 x 2 x 256 = 5120),
    # so its tails ``[1, 3, 5120]`` outnumber the activations (PERF.md section 7).
    assert real_keys()["mamba_d_ssm"] + 2 * real_keys()["mamba_n_groups"] * real_keys()["mamba_d_state"] == real_keys()["hidden_size"]
    for name in ("prefill_dev_ms_per_ktok", "prefill_mfu_share.tpot"):
        assert CELL not in per_layer[name]["workloads"], name
    for name in ("attended_keys_share", "state_hbm_share", "first_token_p50_ms", "slo_good_share.tpot"):
        assert CELL in per_layer[name]["workloads"], name
    assert CELL in per_layer["state_live_share"]["workloads"]  # ``in``: later cells are appended by PRs that may not edit this file
    assert per_layer["state_live_share"]["moves"] == "tpot_p50_ms" and per_layer["state_live_share"]["better"] == "higher"


# ------------------------------------- a fault of the state, through the harness


def test_a_carry_lost_at_chunk_boundaries_is_not_correct():
    """The cell's rehearsal with the chunkwise recurrence starting every
    chunk from a zero state (scripts/solar_state_fault.py, which takes
    the family from the workload, puts the fault into the serve phase of
    the benchmark's own launcher): replies well formed, nothing built in
    the window, and ``correct`` false by the comparison with the
    reference, because the seeded decays remember past a chunk boundary
    (Mamba-2's ranges: heads that keep hundreds of tokens beside heads
    that keep two)."""
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    proc = subprocess.run(
        [sys.executable, "scripts/solar_state_fault.py", "--fault", "zero_carry", "--",
         "--workload", CELL, "--seed", "6", "--seconds", "4", "--trace", "0", "--rehearse-cpu"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads([ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    got = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is False and result["failed"] == 0
    assert got["requests_failed"] == got["replies_malformed"] == got["compiled_in_window"] == 0
    # Sound rehearsals (six seeds) read gap_mean 0.00009-0.00028 against the limit 0.0005, logit_noise up to 0.017 against 0.022.
    limits = harness.load_json(harness.rehearse_path(FAMILY))["check"]
    assert got["gap_mean"] > 2 * limits["gap_mean"] and got["logit_noise"] > limits["logit_noise"]
