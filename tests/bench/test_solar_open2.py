"""The solar_open2 family at the rehearsal widths on the CPU: its plain
reference against the program (full forward; prefill then decode through
the cache; and through the serving pools: whole-prompt prefill, prefill in
chunks with a padded tail, decode after either, a reused slot), what the
program declines for a model with per-slot state, the int8 weights told
apart, the cost functions' goldens and the configuration file's keys.

Tolerance ``F32_TOL``: program and reference both in float32 at highest
matmul precision over four layers differ by the order of their sums only
(the chunkwise delta rule against the token-by-token one included)."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs, harness
from benchmarks.costs import solar_open2 as cost
from benchmarks.reference import common
from benchmarks.weights import make_weights

FAMILY = "solar_open2"
CONFIG = "benchmarks/configs/solar-open2-250b-4l-ep8.json"
F32_TOL = 2e-4
PAGE = 16


def build(seed=3, positions=256):
    keys = harness.model_keys(harness.load_json(f"benchmarks/configs/rehearse/{FAMILY}.json"))
    keys["max_position_embeddings"] = positions
    ref, adapter = harness.family_modules(FAMILY)
    weights = make_weights(ref.weight_specs(keys), seed)
    cls, pc = adapter.program_model(keys, {"moe_dispatch": "sorted"})
    pc32 = dataclasses.replace(pc, dtype=jnp.float32)
    return keys, ref, adapter.to_program(weights, keys), weights, cls, pc32


@pytest.fixture(scope="module")
def built():
    return build()


def tokens_of(n, keys, seed):
    return jax.random.randint(jax.random.key(seed), (n,), 1, keys["vocab_size"])


# ------------------------------------------- reference against program


def test_full_forward_agrees(built):
    keys, ref, params, weights, cls, pc32 = built
    tokens = tokens_of(96, keys, 1)
    want, margin = ref.logits(weights, keys, tokens, jnp.arange(96))
    with jax.default_matmul_precision("highest"):
        got = cls(pc32).apply({"params": params}, tokens[None], return_aux=False)[0]
    assert float(jnp.std(want)) > 0.5, "seeded weights give logits of unit scale"
    assert margin.shape == (96,) and bool(jnp.all(margin >= 0)) and float(jnp.min(margin)) < 0.05
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


def test_prefill_then_decode_through_the_cache_agrees(built):
    keys, ref, params, weights, cls, pc32 = built
    n_prompt, n_new = 40, 24
    tokens = tokens_of(n_prompt + n_new, keys, 2)
    want, _ = ref.logits(weights, keys, tokens, jnp.arange(n_prompt + n_new))
    model = cls(pc32.decode_config())

    def apply(cache, toks, pos):
        out, new = model.apply(
            {"params": params, **cache}, toks, positions=pos,
            segment_ids=jnp.ones_like(toks), mutable=["cache"], return_aux=False,
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
        got = cls(pc32).apply({"params": adapter.to_program(rounded, keys)}, tokens[None], return_aux=False)[0]
    assert float(jnp.max(jnp.abs(got - want))) > 50 * F32_TOL


def test_the_selection_bias_decides_some_choices(built):
    """The seeded bias (standard deviation 0.01) changes the chosen set
    for some tokens, so a program that dropped it would not agree."""
    keys, ref, _, weights, _, _ = built
    x = jax.random.normal(jax.random.key(0), (512, keys["hidden_size"]))
    with_bias, _ = ref.route(weights, "layers.0.", keys, x)
    no_bias = {**weights, "layers.0.moe.router_bias": jnp.zeros_like(weights["layers.0.moe.router_bias"])}
    without, _ = ref.route(no_bias, "layers.0.", keys, x)
    moved = jnp.any((with_bias > 0) != (without > 0), axis=-1)
    assert 0 < int(moved.sum()) < 256


def test_the_eight_shares_add_up_to_the_uncut_reference():
    """Section 4's share test on the reference: the routed parts of the
    shares (here two of eight experts each at these widths: four shares),
    with the shared expert counted once, are the uncut layer's output."""
    keys, ref, _, _, _, _ = build()
    whole = {**keys, "n_routed_experts": 16, "n_routed_experts_published": 16}
    w = make_weights(ref.weight_specs(whole), 7)
    x = jax.random.normal(jax.random.key(1), (48, keys["hidden_size"]))
    p = "layers.2."
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(w, p, whole, x)
        shared = common.swiglu(x, w[p + "moe.shared.gate"], w[p + "moe.shared.up"], w[p + "moe.shared.down"])
        total = jnp.zeros_like(want)
        for first in range(0, 16, 4):
            part = {**keys, "n_routed_experts": 4, "n_routed_experts_published": 16}
            wp = {**w, **{p + f"moe.experts.{n}": w[p + f"moe.experts.{n}"][first:first + 4] for n in ("gate", "up", "down")}}
            total = total + ref.moe(wp, p, part, x, first=first)[0] - shared
    assert float(jnp.max(jnp.abs(total + shared - want))) < 1e-5


def test_state_stays_finite_over_8192_positions(built):
    """The seeded decays (Kimi Linear's initial ranges, ``decay_leaves``)
    over the longest context the cell admits: the recurrence neither
    overflows nor dies, and forgets as the configuration file states."""
    keys, ref, _, weights, _, _ = built
    x = jax.random.normal(jax.random.key(2), (8192, keys["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = ref.kda_inputs(weights, "layers.1.", keys, x)
        o = jax.jit(ref.delta_rule)(q, k, v, g, beta)
    alpha = jnp.exp(g)
    assert bool(jnp.all(jnp.isfinite(o))) and 1e-3 < float(jnp.std(o[-512:])) < 1e2
    assert 0.88 < float(jnp.median(alpha)) < 0.97, "the decay spread the configuration file states"
    # A channel's memory in tokens, 1 / its mean log-decay: a few tokens
    # to hundreds, so a carry lost 64 positions back is still missed.
    memory = 1.0 / jnp.mean(-g, axis=0).reshape(-1)
    assert 4 < float(jnp.median(memory)) < 16 and 0.04 < float(jnp.mean(memory > 64)) < 0.2


def test_decay_leaves_are_kimi_linears_initial_ranges():
    ref, _ = harness.family_modules(FAMILY)
    z = jax.random.normal(jax.random.key(0), (4096,)).astype(jnp.bfloat16)
    a_log, dt_bias = ref.decay_leaves(z, z)
    a, dt = jnp.exp(a_log), jax.nn.softplus(dt_bias)
    assert a_log.dtype == dt_bias.dtype == jnp.float32
    assert 1.0 <= float(a.min()) < 1.2 and 15.8 < float(a.max()) <= 16.0
    assert 1e-3 <= float(dt.min()) * 1.0001 < 1.2e-3 and 0.09 < float(dt.max()) <= 0.1 * 1.0001
    assert 7.5 < float(jnp.median(a)) < 9.5 and 0.008 < float(jnp.median(dt)) < 0.0125


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


def test_whole_prompt_prefill_then_decode_through_the_pool(built):
    keys = built[0]
    prompt = tokens_of(40, keys, 11).tolist()
    pool = pool_of(built)
    _, first = admit_whole(pool, 1, prompt, 16, 40)  # paged rows prefill at their exact width
    check_row(built, pool, 1, prompt, first)


def test_left_padded_prefill_then_decode_through_the_contiguous_pool(built):
    """The contiguous pool pads a prompt on the LEFT to its bucket: the
    padding is the row's empty past (zero state, zero convolution inputs)
    and the per-slot state rides through ``pool_cache`` and ``insert``."""
    from tpufw.infer import SamplingConfig
    from tpufw.infer import slots

    keys, _, params, _, cls, pc32 = built
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=128))
    pool = slots.SlotPool.create(model, params, 3, sampling=SamplingConfig(temperature=0.0))
    assert pool.state_bytes > 0
    prompt = tokens_of(40, keys, 15).tolist()
    with jax.default_matmul_precision("highest"):
        cache, _, first, _, seen = slots.prefill_row(
            model, params, prompt, jax.random.key(0), sampling=pool.sampling, eos_id=None, pad_to=64)
        pool.insert(2, cache, first, len(prompt), 16, row_seen=seen)
    check_row(built, pool, 2, prompt, first)


@pytest.mark.parametrize("n_prompt,chunk_pages", [(40, 1), (40, 2), (75, 2), (96, 3)])
def test_chunked_prefill_with_a_padded_tail_then_decode_through_the_pool(built, n_prompt, chunk_pages):
    """40 tokens = chunks of 16, 16 and 8 padded to 16 (or 32 and a padded
    8); 75 = 32, 32 and 11 padded to 16; 96 = two whole chunks of 48: the
    state and the convolutions' tails ride in the row twin from chunk to
    chunk and do not move on the padding."""
    keys = built[0]
    prompt = tokens_of(n_prompt, keys, 12).tolist()
    pool = pool_of(built)
    cp = admit_chunked(pool, 2, prompt, 16, chunk_pages)
    assert cp.n_chunks == -(-n_prompt // (chunk_pages * PAGE))
    check_row(built, pool, 2, prompt, cp.first_int)


def state_leaves(tree):
    from tpufw.infer.slots import STATE_LEAVES

    return {
        jax.tree_util.keystr(p): leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if str(getattr(p[-1], "key", p[-1])) in STATE_LEAVES
    }


def test_a_reused_slot_holds_nothing_of_the_longer_row_before_it(built):
    keys = built[0]
    pool = pool_of(built, n_slots=2)
    long_prompt = tokens_of(120, keys, 13).tolist()
    admit_chunked(pool, 0, long_prompt, 24, 2)
    decode(pool, 8)
    before = {k: np.asarray(v) for k, v in state_leaves(pool.cache).items()}
    assert len(before) == 6 and all(np.abs(v[0]).max() > 0 for v in before.values())
    assert pool.state_bytes == sum(v.nbytes for v in before.values())
    pool.release_slot(0)
    short = tokens_of(20, keys, 14).tolist()
    row_cache, first = admit_whole(pool, 0, short, 16, 20)
    row = state_leaves(row_cache)
    for path, leaf in state_leaves(pool.cache).items():
        assert bool(jnp.all(leaf[0] == row[path][0])), path  # the new row's, to the bit
    check_row(built, pool, 0, short, first)


# ---------------------------------------------------------- the declines


def test_shared_pages_are_not_attached_and_the_decline_is_counted(built):
    """Two prompts with a common first 1,024 tokens: the second gets no
    page of the first (its linear-attention state would start from zero),
    the pool says why (the scheduler counts it: the next test), and both rows give the reference's
    logits."""
    big = build(positions=2048)
    keys = big[0]
    common_part = tokens_of(1024, keys, 21).tolist()
    a = common_part + tokens_of(16, keys, 22).tolist()
    b = common_part + tokens_of(32, keys, 23).tolist()
    pool = pool_of(big, n_slots=2, positions=2048, prefix_cache=True)
    assert pool.prefix is None and pool.prefix_decline == "state_layers"
    cpa = admit_chunked(pool, 0, a, 8, 32)
    cpb = admit_chunked(pool, 1, b, 8, 32)
    assert cpa.shared_n == cpb.shared_n == 0 and pool.prefix_hits == 0
    assert not set(cpa.page_ids) & set(cpb.page_ids)
    check_row(big, pool, 1, b, cpb.first_int, n_steps=4)
    pool.register_prefix(a, cpa.page_ids)  # adopts none
    assert pool.allocator.held == set()


def test_the_scheduler_counts_the_declined_lookups(built):
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
    assert reg.counter("tpufw_serve_prefix_declined_total").value(reason="state_layers") == 2
    assert reg.counter("tpufw_serve_prefix_hits_total").value() == 0
    assert reg.counter("tpufw_serve_prefix_misses_total").value() == 0
    assert reg.gauge("tpufw_serve_state_bytes").value() == sched._pool.state_bytes > 0
    assert reg.gauge("tpufw_serve_state_slots").value() == sched.n_slots
    text = reg.render()
    assert 'tpufw_serve_prefix_declined_total{reason="state_layers"} 2' in text
    # The second answer is the reference's greedy continuation of ITS prompt.
    prompt = shared + tokens_of(8, keys, 33).tolist()
    seq = prompt + outs[1]
    want, _ = ref.logits(weights, keys, jnp.asarray(seq[:-1]), jnp.arange(len(prompt) - 1, len(seq) - 1))
    served = want[jnp.arange(5), jnp.asarray(outs[1])]
    assert float(jnp.max(jnp.max(want, axis=-1) - served)) < 1e-3


def test_export_splice_and_speculation_refuse_the_family_by_name(built):
    from tpufw.infer import SamplingConfig
    from tpufw.workloads import serve

    keys = built[0]
    pool = pool_of(built, n_slots=2)
    cp = admit_chunked(pool, 0, tokens_of(24, keys, 41).tolist(), 8, 1)
    with pytest.raises(ValueError, match=r"export_slot: SolarOpen2 keeps per-slot state"):
        pool.export_slot(0)
    with pytest.raises(ValueError, match=r"splice_slot: SolarOpen2 keeps per-slot state"):
        pool.splice_slot(1, {}, cp.page_ids)
    with pytest.raises(ValueError, match=r"speculative decoding: SolarOpen2"):
        pool.spec_steps(np.zeros((2, 2), np.int32), jax.random.key(0))
    _, _, params, _, cls, pc32 = built
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=256))
    sched = serve._SlotScheduler(
        model, params, eos_id=None, default_sampling=SamplingConfig(temperature=0.0),
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=True, prefill_chunk_pages=1, spec_k=2,
    )
    with pytest.raises(ValueError, match=r"TPUFW_SERVE_SPEC_K=2: SolarOpen2 keeps per-slot state"):
        sched.submit([tokens_of(24, keys, 42).tolist()], 4)


def test_a_model_of_keys_and_values_still_hits_exports_and_splices():
    """The declines are the state's alone: a Llama pool has a trie, hits
    it on a repeated prompt, and exports and splices a slot as before."""
    from tpufw.infer import SamplingConfig
    from tpufw.infer import pages
    from tpufw.models import LLAMA_CONFIGS, Llama

    cfg = dataclasses.replace(LLAMA_CONFIGS["llama3_tiny"].decode_config(), max_seq_len=64)
    row = Llama(cfg)
    params = jax.jit(row.init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    paged = Llama(dataclasses.replace(cfg, kv_page=PAGE, kv_pages=2 * 4 + 1))
    pool = pages.PagedSlotPool.create_paged(paged, row, params, 2, sampling=SamplingConfig(temperature=0.0), eos_id=None)
    assert pool.prefix is not None and pool.prefix_decline == "" and pool.state_bytes == 0
    prompt = list(range(1, 41))
    admit_chunked(pool, 0, prompt, 4, 1)
    cp = admit_chunked(pool, 1, prompt, 4, 1)
    assert cp.shared_n == 2 and pool.prefix_hits == 1
    state = pool.export_slot(0)
    ids = pool.allocator.alloc(state["n_pages"])
    pool.release_slot(0)
    pool.splice_slot(0, state, ids)
    assert pool.slot_pages[0] == ids


# ------------------------------------------------- costs and configuration


def real_keys():
    return harness.model_keys(harness.load_json(CONFIG))


def test_cost_goldens():
    c = real_keys()
    assert cost.parameters(c) == 4_717_576_192 == harness.load_json(CONFIG)["memory"]["parameters"]
    assert cost.active_matmul_params(c) == 1_458_601_984
    assert cost.layer_params(c)["gqa"] == 109_051_904 and cost.layer_params(c)["kda"] == 137_723_904
    assert cost.cache_bytes_per_token(c) == costs.cache_bytes_per_token(FAMILY, c) == 4096
    assert cost.state_bytes_per_row(c) == 13_025_280 == harness.load_json(CONFIG)["memory"]["state_bytes_per_slot"]
    assert cost.kda_flops_per_token(c) == 28_311_552.0
    assert costs.decode_step_bytes(FAMILY, c, 3, [2304, 2304, 2304]) == 3265991475.2000003
    assert costs.decode_step_bytes(FAMILY, c, 0, []) == 2791374848.0
    assert costs.prefill_flops(FAMILY, c, [2048, 7104]) == 13115969241088.0
    assert costs.prefill_chunk_flops(FAMILY, c, 512, [2048, 7104]) == pytest.approx(733580308565.93)


def test_state_counts_by_the_row_and_keys_by_the_token():
    c = real_keys()
    one, long = cost.decode_step_bytes(c, [100]), cost.decode_step_bytes(c, [8000])
    assert long - one == 7900 * 4096, "K/V grow by the token, in the one softmax layer"
    none = cost.decode_step_bytes(c, [])
    touched = costs.expected_experts_touched(320, 8, 1, 40)
    assert touched == pytest.approx(1.0)  # 8 of 320 chosen, 40 held: one in expectation
    assert one - none == pytest.approx(
        2 * (4 * touched * 15_728_640 + 4096) + 100 * 4096 + 2 * 13_025_280)
    # Causal pairs in one layer of four; the KDA blocks by the token.
    p = cost.prefill_flops(c, [4096]) - cost.prefill_flops(c, [2048]) * 2
    assert p == pytest.approx(2.0 * 64 * 256 * (4096 * 4097 / 2 - 2 * 2048 * 2049 / 2) - 2.0 * 805_306_368)


def test_catalog_keys_kept_or_listed_as_reduced():
    # The catalog's row as ISSUE 28 drew it, kept beside this file: a test
    # reads nothing outside its checkout.
    with open(os.path.join(os.path.dirname(__file__), "solar_open2_catalog_row.json")) as f:
        row = json.load(f)
    assert row["name"] == "Solar-Open2-250B"
    config = harness.load_json(CONFIG)
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == ["max_position_embeddings", "n_routed_experts", "num_hidden_layers"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value and "->" in config["reduced"][key]
        else:
            assert config[key] == value, key
    assert config["vocab_size"] == 196_608 and config["n_routed_experts_published"] == 320
    assert config["n_routed_experts"] == 40 and config["num_experts_per_tok"] == 8
    assert config["memory"]["weights_bytes_bf16"] == 2 * config["memory"]["parameters"]
    for k in ("scoring", "kda", "gqa_gate", "hidden_act", "weights", "decay_spread", "dtype"):
        assert k in config["assumed"]
    assert "eight chips share each" in config["deployment"].lower().replace("each group of eight chips shares", "eight chips share each")


def test_the_reference_stands_alone_and_covers_every_answer():
    ref, _ = harness.family_modules(FAMILY)
    with open(ref.__file__) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+tpufw", src, re.M), "the reference imports nothing of the program"
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        config = harness.load_json(harness.config_entry(bench, w["config"])["file"])
        if config["family"] == FAMILY:
            mix = harness.load_json(harness.traffic_path(w["traffic"]))
            assert mix["output"]["cap"] <= ref.MAX_AT and mix["rehearse"]["output"]["cap"] <= ref.MAX_AT
            assert mix["prompt"]["cap"] + mix["output"]["cap"] <= config["max_position_embeddings"]


def test_new_readers_report_nothing_where_there_is_nothing_to_read():
    from benchmarks.metrics import prefill_dev_ms_per_ktok, state_hbm_share

    obs = {"prom1": {}, "rehearse": False, "trace": None, "device": {"kind": "TPU v5 lite"}}
    assert state_hbm_share.read(obs) is None and prefill_dev_ms_per_ktok.read(obs) is None
    obs["prom1"]["tpufw_serve_state_bytes"] = 8 * 13_025_280.0
    assert state_hbm_share.read(obs) == pytest.approx(0.651264)
    obs["trace"] = {"programs": {"jit__prefill_chunk_jit": {"n": 4, "seconds": 0.8, "tokens": 2048},
                                 "jit__decode_steps_jit": {"n": 9, "seconds": 3.0}}}
    assert prefill_dev_ms_per_ktok.read(obs) == pytest.approx(390.625)
    obs["trace"]["programs"]["jit__prefill_chunk_jit"]["widths_unread"] = 1
    assert prefill_dev_ms_per_ktok.read(obs) is None


# ------------------------------------- a fault of the state, through the harness


def test_a_carry_lost_at_chunk_boundaries_is_not_correct():
    """The cell's rehearsal with the chunkwise delta rule starting every
    chunk from a zero state (scripts/solar_state_fault.py puts the fault
    into the serve phase of the benchmark's own launcher): replies well
    formed, nothing built in the window, and ``correct`` false by the
    comparison with the reference, because the seeded decays remember
    past a chunk boundary (Kimi Linear's ranges: a third of the channels
    keep more than the 16-32 tokens of a rehearsal prompt's last chunk)."""
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    proc = subprocess.run(
        [sys.executable, "scripts/solar_state_fault.py", "--fault", "zero_carry", "--",
         "--workload", "solar2-longdoc-answers", "--seed", "5", "--seconds", "4", "--trace", "0", "--rehearse-cpu"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads([ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    got = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is False and result["failed"] == 0
    assert got["requests_failed"] == got["replies_malformed"] == got["compiled_in_window"] == 0
    # Sound rehearsals read gap_mean 0.003-0.011 against the limit 0.05.
    assert got["gap_mean"] > 0.05 and got["gap_max"] > 0.5
