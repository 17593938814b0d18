"""The laguna family at the rehearsal widths on the CPU: its plain
reference against the program (full forward; prefill then decode through
the cache; and through the serving pools: whole-prompt prefill, prefill in
chunks with a padded tail, decode after either, a reused slot), the window
layers' ring against the masked whole row, YaRN and the half-rotary rope
against a hand-written formula, the eight shares of an expert layer, what
the program declines for a model with window rings, the int8 weights told
apart, the cost functions' goldens, the configuration file's keys, the new
metrics' readers, and the window fault through the harness.

Tolerance ``F32_TOL``: program and reference both in float32 at highest
matmul precision over eight layers differ by the order of their sums only
(measured 7e-6 over 96 positions; bfloat16 activations read 0.03-0.1)."""

import dataclasses
import functools
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs, harness, traffic
from benchmarks.costs import laguna as cost
from benchmarks.reference import common
from benchmarks.weights import make_weights

FAMILY = "laguna"
CONFIG = "benchmarks/configs/laguna-s-2.1-8l-ep8.json"
CELL = "laguna-repo-context"
F32_TOL = 2e-4
PAGE = 16
WINDOW = 16  # the rehearsal widths' sliding_window


LISTS = ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer")


def build(seed=3, positions=256, layers=4):
    """The rehearsal widths, cut to ``layers`` layers: one period (a
    global dense layer, three window layers with experts) compiles in half
    the time of the rehearsal's two and has every mechanism."""
    keys = harness.model_keys(harness.load_json(f"benchmarks/configs/rehearse/{FAMILY}.json"))
    keys.update({k: keys[k][:layers] for k in LISTS}, num_hidden_layers=layers, max_position_embeddings=positions)
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


def test_full_forward_agrees():
    keys, ref, params, weights, cls, pc32 = build(layers=8)  # the rehearsal's own two periods
    tokens = tokens_of(96, keys, 1)  # six windows long
    want, margin = jax.jit(lambda w, t: ref.logits(w, keys, t, jnp.arange(96)))(weights, tokens)
    forward = lambda pc: jax.jit(lambda p, t: cls(pc).apply({"params": p}, t[None], return_aux=False)[0])
    with jax.default_matmul_precision("highest"):
        got = forward(pc32)(params, tokens)
    assert float(jnp.std(want)) > 0.5, "seeded weights give logits of unit scale"
    assert margin.shape == (96,) and bool(jnp.all(margin >= 0)) and float(jnp.min(margin)) < 0.05
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL
    # bfloat16 where float32 is stated fails the same comparison.
    low = forward(dataclasses.replace(pc32, dtype=jnp.bfloat16))(params, tokens)
    assert float(jnp.max(jnp.abs(low - want))) > 20 * F32_TOL


@pytest.mark.parametrize("n_prompt", [8, WINDOW, 40, 150])
def test_prefill_then_decode_through_the_cache_agrees(built, n_prompt):
    """A prompt shorter than the window, equal to it, 2.5 and 9 windows
    long, then 24 decode steps through the rings and the rows."""
    keys, ref, params, weights, cls, pc32 = built
    n_new = 24
    tokens = tokens_of(n_prompt + n_new, keys, 2)
    want = reference(built, tokens, jnp.arange(n_prompt + n_new))
    model = cls(pc32.decode_config())

    @jax.jit
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
    ring = cache["cache"]["layer_1"]["attn"]
    assert ring["ring_key"].shape == (1, WINDOW, 2, 16), "a window layer holds the window, not the row"
    assert "cached_key" in cache["cache"]["layer_0"]["attn"] and "cached_key" not in ring


def test_int8_weights_are_told_apart(built):
    keys, ref, params, weights, cls, pc32 = built
    tokens = tokens_of(64, keys, 4)
    want = reference(built, tokens, jnp.arange(64))
    rounded = {
        k: v if any(s in k for s in ref.INT8_KEEP) else common.int8_round_trip(v, v.ndim - 2)
        for k, v in weights.items()
    }
    _, adapter = harness.family_modules(FAMILY)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: cls(pc32).apply({"params": p}, tokens[None], return_aux=False)[0])(adapter.to_program(rounded, keys))
    assert float(jnp.max(jnp.abs(got - want))) > 50 * F32_TOL


def test_the_gates_and_the_router_are_not_degenerate(built):
    """Seeded weights: the per-head gates are not all 0.5 and the top-k is
    not a tie over the router's width."""
    keys, ref, _, weights, _, _ = built
    x = common.rms_norm(jax.random.normal(jax.random.key(0), (256, keys["hidden_size"])), jnp.ones(64), 1e-6)
    gate = jax.nn.sigmoid(x @ common.up(weights["layers.1.gate_proj"]))
    assert gate.shape == (256, 6) and float(gate.min()) < 0.2 and float(gate.max()) > 0.8
    gates, margin = ref.route(weights, "layers.1.", keys, x)
    assert gates.shape == (256, 16) and bool(jnp.all(jnp.sum(gates > 0, axis=-1) == 2))
    assert float(jnp.max(jnp.abs(jnp.sum(gates, axis=-1) - 2.5))) < 1e-5, "renormalised, times the routed scaling factor"
    assert float(jnp.median(margin)) > 0.01


def test_the_eight_shares_add_up_to_the_uncut_reference():
    """Section 4's share test on the reference: the routed parts of the
    shares (here four experts each at these widths: four shares), with
    the shared expert counted once, are the uncut layer's output."""
    keys, ref, _, _, _, _ = build()
    whole = {**keys, "num_experts": 16, "num_experts_published": 16}
    w = make_weights(ref.weight_specs(whole), 7)
    x = jax.random.normal(jax.random.key(1), (48, keys["hidden_size"]))
    p = "layers.2."
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(w, p, whole, x)
        shared = common.swiglu(x, w[p + "moe.shared.gate"], w[p + "moe.shared.up"], w[p + "moe.shared.down"])
        total = jnp.zeros_like(want)
        for first in range(0, 16, 4):
            part = {**keys, "num_experts": 4, "num_experts_published": 16}
            wp = {**w, **{p + f"moe.experts.{n}": w[p + f"moe.experts.{n}"][first:first + 4] for n in ("gate", "up", "down")}}
            total = total + ref.moe(wp, p, part, x, first=first)[0] - shared
    assert float(jnp.max(jnp.abs(total + shared - want))) < 1e-5


def test_the_programs_share_is_the_references(built):
    """The program's expert layer, told it holds experts 0-7 of 16, gives
    the reference's share: router over all 16, the held ones' part."""
    from tpufw.models.deepseek import DeepseekMoE

    keys, ref, params, weights, cls, pc32 = built
    x = jax.random.normal(jax.random.key(3), (1, 40, keys["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got, _ = DeepseekMoE(pc32).apply({"params": params["layer_3"]["moe"]}, x)
        want, _ = ref.moe(weights, "layers.3.", keys, x[0])
    assert pc32.experts_held == (0, 8) and float(jnp.max(jnp.abs(got[0] - want))) < 1e-5


def test_yarn_and_the_half_rotary_rope_against_a_hand_written_formula():
    """Laguna-S-2.1's published ``rope_parameters`` through the program's
    rope: the YaRN frequencies over the first 64 dimensions of a head,
    cosines and sines times the published attention factor, the other 64
    dimensions untouched; and the window layers' plain rope over all 128."""
    from tpufw.models.laguna import ROPE_FULL, ROPE_SLIDING
    from tpufw.models.llama import apply_rope

    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 128))
    pos = jnp.array([[0, 1, 77, 9000, 1_000_000]])
    got = apply_rope(x, pos, ROPE_FULL.theta, ROPE_FULL.scaling, ROPE_FULL.rotary_dim)
    dim, base, factor, original = 64, 500_000.0, 128.0, 8192
    corr = lambda rot: dim * math.log(original / (rot * 2 * math.pi)) / (2 * math.log(base))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    want = np.array(x, np.float64)
    for j in range(dim // 2):
        plain = base ** (-2.0 * j / dim)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        freq = plain / factor * ramp + plain * (1.0 - ramp)
        ang = np.asarray(pos[0], np.float64) * freq
        cos, sin = (1.4852030263919618 * np.cos(ang))[:, None], (1.4852030263919618 * np.sin(ang))[:, None]
        a, b = np.array(x[0, :, :, j], np.float64), np.array(x[0, :, :, j + dim // 2], np.float64)
        want[0, :, :, j], want[0, :, :, j + dim // 2] = a * cos - b * sin, b * cos + a * sin
    assert (low, high) == (9, 18) and abs(1.4852030263919618 - (0.1 * math.log(128) + 1)) < 1e-12
    # float32 angles at position 1e6 carry 1e6 x 6e-8 = 0.06 rad of the fastest frequency's rounding.
    assert np.max(np.abs(np.asarray(got)[..., :4, :, :] - want[..., :4, :, :])) < 2e-3
    assert np.max(np.abs(np.asarray(got)[..., :3, :, :] - want[..., :3, :, :])) < 2e-5
    assert bool(jnp.all(got[..., 64:] == x[..., 64:])), "the other half of each head passes unrotated"
    plain = apply_rope(x, pos, ROPE_SLIDING.theta, ROPE_SLIDING.scaling, ROPE_SLIDING.rotary_dim)
    ang = 77.0 * 10_000.0 ** (-2.0 * 5 / 128)
    a, b = float(x[0, 2, 1, 5]), float(x[0, 2, 1, 69])
    assert abs(float(plain[0, 2, 1, 5]) - (a * math.cos(ang) - b * math.sin(ang))) < 1e-5
    assert abs(float(plain[0, 2, 1, 69]) - (b * math.cos(ang) + a * math.sin(ang))) < 1e-5


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


@functools.partial(jax.jit, static_argnames="model")
def _next_logits(model, params, cache, token, pos):
    from tpufw.infer.generate import _model_apply

    apply = _model_apply(model, params)
    return apply(cache, token[:, None], pos[:, None], jnp.ones((token.shape[0], 1), jnp.int32))[0][:, -1]


def peek(pool):
    """Next-token logits [slots, V] out of the pool's own cache: what its
    decode step computes before it samples (nothing is donated)."""
    with jax.default_matmul_precision("highest"):
        return _next_logits(pool.model, pool.params, pool.cache, pool.token, pool.pos)


def reference(built, seq, at):
    """The reference's logits after the positions ``at`` of ``seq``, as one
    compiled program a shape (its eager pass builds every block anew)."""
    keys, ref, _, weights, _, _ = built
    return jax.jit(lambda w, t, a: ref.logits(w, keys, t, a)[0])(weights, jnp.asarray(seq), jnp.asarray(at))


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
    prompt and again after ``n_steps`` decode steps through the pool (one
    pass of the reference over the whole sequence: it is causal)."""
    keys, ref, _, weights, _, _ = built
    after_prompt = peek(pool)[slot]
    seq = list(prompt) + [first] + decode(pool, n_steps)[slot].tolist()
    # Past one attention block the reference wants whole blocks: zeros
    # after the real tokens, which causality keeps out.
    pad = -len(seq) % ref.QUERY_BLOCK if len(seq) > ref.QUERY_BLOCK else 0
    at = [len(prompt) - 1, len(prompt), len(seq) - 1]
    want = reference(built, seq + [0] * pad, at)
    assert int(jnp.argmax(want[0])) == first, "the prefill sampled the reference's first token"
    assert float(jnp.max(jnp.abs(after_prompt - want[1]))) < F32_TOL
    assert float(jnp.max(jnp.abs(peek(pool)[slot] - want[2]))) < F32_TOL


def test_whole_prompt_prefill_then_decode_through_the_pool(built):
    keys = built[0]
    prompt = tokens_of(40, keys, 11).tolist()
    pool = pool_of(built)
    _, first = admit_whole(pool, 1, prompt, 16, 40)  # 40 > the ring: the call keeps its last 16
    check_row(built, pool, 1, prompt, first)


def test_left_padded_prefill_then_decode_through_the_contiguous_pool(built):
    """The contiguous pool pads a prompt on the LEFT to its bucket: the
    padding is never written to a ring (its slots stay segment 0) and the
    rings ride through ``pool_cache`` and ``insert``."""
    from tpufw.infer import SamplingConfig
    from tpufw.infer import slots

    keys, _, params, _, cls, pc32 = built
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=128))
    pool = slots.SlotPool.create(model, params, 3, sampling=SamplingConfig(temperature=0.0))
    assert pool.window_bytes > 0 and pool.state_bytes == 0
    prompt = tokens_of(40, keys, 15).tolist()
    with jax.default_matmul_precision("highest"):
        cache, _, first, _, seen = slots.prefill_row(
            model, params, prompt, jax.random.key(0), sampling=pool.sampling, eos_id=None, pad_to=64)
        pool.insert(2, cache, first, len(prompt), 16, row_seen=seen)
    check_row(built, pool, 2, prompt, first)


@pytest.mark.parametrize("n_prompt,chunk_pages", [(8, 1), (16, 1), (40, 1), (75, 2), (150, 2)])
def test_chunked_prefill_with_a_padded_tail_then_decode_through_the_pool(built, n_prompt, chunk_pages):
    """8 tokens = half a window in one padded chunk; 16 = the window in
    one whole chunk; 40 = chunks of 16, 16 and 8 padded to 16; 75 = 32, 32
    and 11 padded to 16, so the second boundary (64) is where a window
    (60-75) has begun; 150 = nine windows in chunks of two. The ring rides in the row twin from chunk to chunk, the
    padding is not written to it, and each chunk reads the ring as the
    chunk before left it beside its own tokens."""
    keys = built[0]
    prompt = tokens_of(n_prompt, keys, 12).tolist()
    pool = pool_of(built)
    cp = admit_chunked(pool, 2, prompt, 32, chunk_pages)
    assert cp.n_chunks == -(-n_prompt // (chunk_pages * PAGE))
    check_row(built, pool, 2, prompt, cp.first_int, n_steps=WINDOW + 4)  # the decode steps lap the ring


def ring_leaves(tree):
    from tpufw.ops import kv_store

    return {
        jax.tree_util.keystr(p): leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if kv_store.path_role(p).kind == kv_store.RING
    }


def test_a_reused_slot_holds_nothing_of_the_longer_row_before_it(built):
    keys = built[0]
    pool = pool_of(built, n_slots=2)
    long_prompt = tokens_of(120, keys, 13).tolist()
    admit_chunked(pool, 0, long_prompt, 24, 2)
    decode(pool, 8)
    before = {k: np.asarray(v) for k, v in ring_leaves(pool.cache).items()}
    assert len(before) == 3 * 4 and all(np.abs(v[0]).max() > 0 for v in before.values())
    assert pool.window_bytes == sum(v.nbytes for v in before.values()) == 3 * 2 * WINDOW * (2 * 2 * 16 * 4 + 8)
    assert pool.window_slots == 3 * 2 * WINDOW and pool.window_keys(3) == (3 * 3 * WINDOW, 3 * 3 * 256)
    assert pool.window_keys(1, 32) == (3 * (WINDOW + 32), 3 * 256)
    pool.release_slot(0)
    short = tokens_of(10, keys, 14).tolist()  # shorter than the ring: six of its slots stay empty
    row_cache, first = admit_whole(pool, 0, short, 16, 10)
    row = ring_leaves(row_cache)
    for path, leaf in ring_leaves(pool.cache).items():
        assert bool(jnp.all(leaf[0] == row[path][0])), path  # the new row's, to the bit
        if path.endswith("['ring_segment']"):
            assert int(jnp.sum(leaf[0] > 0)) == 10, "nothing of the longer row is left to attend"
    check_row(built, pool, 0, short, first)


def test_the_ring_equals_the_masked_whole_row(built):
    """One window layer's attention module over the same weights with the
    ring and with the masked whole row (a config without the family's
    ``window_ring``: what ``llama.Attention(window=W)`` does for every
    other family): a padded prefill chunk,
    a second chunk and decode steps past a lap of the ring give the same
    output to float32 rounding of the sums (the ring's W + t or W keys
    against the row's 128). The order of the float32 sums is the backend's,
    which picks its matmul by the shape it is handed: the test holds the
    values, not the bits, so that a spelling of the contraction is not
    chosen by what one backend's kernel choice makes bit-equal."""
    from tpufw.models.llama import Attention

    keys, _, params, _, _, pc32 = built
    cfg = dataclasses.replace(pc32.decode_config(), max_seq_len=128)
    p = {"params": params["layer_1"]["attn"]}
    x = jax.random.normal(jax.random.key(5), (2, 80, keys["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(80), (2, 80))
    seg = jnp.ones((2, 80), jnp.int32).at[1, 20:32].set(0)  # row 1: a padded tail in its first chunk
    outs = {}

    class Masked(type(cfg)):
        window_ring = False

    masked = Masked(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)})
    for ring in (True, False):
        mod = Attention(cfg if ring else masked, window=WINDOW, n_heads=6, rope=cfg.rope_sliding)
        cache, got = {}, []
        for lo, hi in [(0, 32), (32, 64)] + [(i, i + 1) for i in range(64, 80)]:
            with jax.default_matmul_precision("highest"):
                y, new = mod.apply({**p, **cache}, x[:, lo:hi], pos[:, lo:hi], seg[:, lo:hi], mutable=["cache"])
            cache = {"cache": new["cache"]}
            got.append(y)
        outs[ring] = jnp.concatenate(got, axis=1)
        assert ("ring_key" in cache["cache"]) == ring
    real = np.asarray(seg > 0)
    diff = np.abs(np.asarray(outs[True]) - np.asarray(outs[False]))[real]
    assert diff.max() < 1e-6, "same keys, same weights: the order of the sums alone"


# ---------------------------------------------------------- the declines


def test_shared_pages_are_not_attached_and_the_decline_is_counted(built):
    """Two prompts with a common first 64 tokens: the second gets no page
    of the first (its window layers would start from an empty ring), the
    pool says why, and both rows give the reference's logits."""
    keys = built[0]
    common_part = tokens_of(64, keys, 21).tolist()
    a = common_part + tokens_of(16, keys, 22).tolist()
    b = common_part + tokens_of(32, keys, 23).tolist()
    pool = pool_of(built, n_slots=2, prefix_cache=True)
    assert pool.prefix is None and pool.prefix_decline == "window_layers"
    cpa = admit_chunked(pool, 0, a, 8, 2)
    cpb = admit_chunked(pool, 1, b, 8, 2)
    assert cpa.shared_n == cpb.shared_n == 0 and pool.prefix_hits == 0
    assert not set(cpa.page_ids) & set(cpb.page_ids)
    check_row(built, pool, 1, b, cpb.first_int, n_steps=4)
    pool.register_prefix(a, cpa.page_ids)  # adopts none
    assert pool.allocator.held == set()


def test_the_scheduler_counts_the_declined_lookups_and_the_window_keys(built):
    """The host's count against the program's shapes: every dispatched
    decode step reads ``window`` ring slots a row in each of the three
    window layers, a prefill chunk ``window + width``, each beside
    ``max_seq_len``; the gauges give the rings' bytes and slots."""
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
    assert reg.counter("tpufw_serve_prefix_declined_total").value(reason="window_layers") == 2
    assert reg.counter("tpufw_serve_prefix_declined_total").value(reason="state_layers") == 0
    assert value("prefix_hits_total") == value("prefix_misses_total") == 0
    pool = sched._pool
    assert reg.gauge("tpufw_serve_window_bytes").value() == pool.window_bytes == 3 * sched.n_slots * WINDOW * (2 * 2 * 16 * 4 + 8)
    assert reg.gauge("tpufw_serve_window_slots").value() == 3 * sched.n_slots * WINDOW
    assert reg.gauge("tpufw_serve_state_bytes").value() == 0
    # The program's shapes: a decode step's window layer reads [slots, W] keys, a 32-token chunk [1, W + 32].
    ring = pool.cache["cache"]["layer_1"]["attn"]["ring_key"]
    assert ring.shape[:2] == (sched.n_slots, WINDOW)
    row = pool.cache_len  # the scheduler's own rung of cache lengths for these prompts
    # A prompt of 72 tokens is chunks of 32, 32 and 8 padded to a page of 16; the rest are steps x slots.
    chunks, steps = 2 * 3, value("row_key_slots_total") / row - 2 * 3
    assert steps > 0 and steps % sched.n_slots == 0
    assert value("window_key_slots_total") == 3 * (2 * (2 * (WINDOW + 32) + (WINDOW + 16)) + steps * WINDOW)
    assert value("window_row_key_slots_total") == 3 * row * (chunks + steps)
    text = reg.render()
    assert 'tpufw_serve_prefix_declined_total{reason="window_layers"} 2' in text
    # The second answer is the reference's greedy continuation of ITS prompt.
    prompt = shared + tokens_of(8, keys, 33).tolist()
    seq = prompt + outs[1]
    want, _ = ref.logits(weights, keys, jnp.asarray(seq[:-1]), jnp.arange(len(prompt) - 1, len(seq) - 1))
    served = want[jnp.arange(5), jnp.asarray(outs[1])]
    assert float(jnp.max(jnp.max(want, axis=-1) - served)) < 1e-3


def test_a_model_without_window_layers_counts_no_window_keys():
    from tpufw.infer import SamplingConfig
    from tpufw.models import LLAMA_CONFIGS, Llama
    from tpufw.workloads import serve

    cfg = dataclasses.replace(LLAMA_CONFIGS["mistral_tiny"].decode_config(), max_seq_len=64)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    metrics = serve._Metrics()
    sched = serve._SlotScheduler(
        model, params, eos_id=None, default_sampling=SamplingConfig(temperature=0.0),
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=True, prefill_chunk_pages=1, metrics=metrics,
    )
    sched.submit([list(range(1, 21))], 4)
    reg = metrics.registry
    # Mistral's one window on every layer keeps the mask over the paged row: no ring, a trie, nothing declined.
    assert sched._pool.window_bytes == 0 and sched._pool.prefix is not None
    assert reg.counter("tpufw_serve_window_key_slots_total").value() == 0
    assert reg.counter("tpufw_serve_window_row_key_slots_total").value() == 0
    assert reg.counter("tpufw_serve_row_key_slots_total").value() > 0
    assert reg.gauge("tpufw_serve_window_bytes").value() == reg.gauge("tpufw_serve_window_slots").value() == 0


def test_export_splice_and_speculation_refuse_the_family_by_name(built):
    from tpufw.infer import SamplingConfig
    from tpufw.workloads import serve

    keys = built[0]
    pool = pool_of(built, n_slots=2)
    cp = admit_chunked(pool, 0, tokens_of(24, keys, 41).tolist(), 8, 1)
    with pytest.raises(ValueError, match=r"export_slot: Laguna keeps a ring of its window layers' last keys"):
        pool.export_slot(0)
    with pytest.raises(ValueError, match=r"splice_slot: Laguna keeps a ring"):
        pool.splice_slot(1, {}, cp.page_ids)
    with pytest.raises(ValueError, match=r"speculative decoding: Laguna keeps a ring"):
        pool.spec_steps(np.zeros((2, 2), np.int32), jax.random.key(0))
    _, _, params, _, cls, pc32 = built
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=256))
    sched = serve._SlotScheduler(
        model, params, eos_id=None, default_sampling=SamplingConfig(temperature=0.0),
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=True, prefill_chunk_pages=1, spec_k=2,
    )
    with pytest.raises(ValueError, match=r"TPUFW_SERVE_SPEC_K=2: Laguna keeps a ring"):
        sched.submit([tokens_of(24, keys, 42).tolist()], 4)


def test_no_window_layer_is_in_a_ladders_switch(built):
    """The lowered decode step of a pool whose rows hold four rungs: the
    global layer's switch (a ``case`` operation) and none for the three
    window layers, whose view has one static length."""
    _, _, params, _, cls, pc32 = built
    from tpufw.ops import kv_store

    cfg = dataclasses.replace(pc32.decode_config(), max_seq_len=16384, kv_page=PAGE, kv_pages=2 * 1024 + 1)
    assert kv_store.key_ladder(16384, PAGE) == (2048, 4096, 8192, 16384)
    model = cls(cfg)
    toks = jnp.zeros((2, 1), jnp.int32)

    def step(p, cache):
        return model.apply({"params": p, "cache": cache}, toks, positions=toks, segment_ids=toks + 1,
                           mutable=["cache"], return_aux=False)

    shapes = jax.eval_shape(lambda p: model.apply({"params": p}, toks, positions=toks, segment_ids=toks + 1,
                                                  mutable=["cache"], return_aux=False)[1]["cache"], params)
    text = jax.jit(step).lower(params, shapes).as_text()
    assert text.count("stablehlo.case") == 1
    assert shapes["layer_1"]["attn"]["ring_key"].shape == (2, WINDOW, 2, 16)
    assert shapes["layer_0"]["attn"]["cached_key"].shape == (2 * 1024 + 1, PAGE, 2, 16)


# ------------------------------------------------- costs and configuration


def real_keys():
    return harness.model_keys(harness.load_json(CONFIG))


def test_cost_goldens():
    c, config = real_keys(), harness.load_json(CONFIG)
    assert cost.parameters(c) == 3_382_493_184 == config["memory"]["parameters"]
    assert cost.active_matmul_params(c) == 1_042_857_984  # 0.735 B besides the head
    p = cost.layer_params(c)
    assert p["full_attention"] == 44_187_648 and p["sliding_attention"] == 63_135_744
    assert p["dense_ffn"] == 113_246_208 and p["expert"] == p["shared"] == 9_437_184 and p["router"] == 786_432
    assert cost.cache_bytes_per_token(c) == costs.cache_bytes_per_token(FAMILY, c) == 8 * 4096
    assert config["memory"]["global_cache_bytes"] == 2 * 8 * 16384 * 4096
    assert config["memory"]["ring_bytes"] == 6 * 8 * 512 * (4096 + 8)
    assert costs.decode_step_bytes(FAMILY, c, 0, []) == 2 * (cost._weights(c, 0.0))
    assert costs.decode_step_bytes(FAMILY, c, 2, [4352, 4352]) == 2340896768.0
    assert costs.prefill_flops(FAMILY, c, [4096]) == 6865607983104.0
    assert costs.prefill_chunk_flops(FAMILY, c, 512, [4096]) == pytest.approx(
        (costs.prefill_flops(FAMILY, c, [4096]) - 2.0 * 308_281_344) / 8)


def test_a_window_caps_a_rows_bytes_and_a_prompts_pairs():
    c = real_keys()
    short, at_window, long = (cost.decode_step_bytes(c, [n]) for n in (100, 512, 16000))
    assert at_window - short == 412 * 8 * 4096, "under the window every layer grows by the token"
    assert long - at_window == (16000 - 512) * 2 * 4096, "past it only the two global layers do"
    assert cost.row_cache_bytes(c, 16000) == 2 * 16000 * 4096 + 6 * 512 * 4096
    # Pairs: n (n + 1) / 2 in a global layer; sum_i min(i, 512) in a window layer.
    assert cost.attended_pairs(c, "sliding_attention", 4096) == sum(min(i, 512) for i in range(1, 4097))
    assert cost.attended_pairs(c, "full_attention", 4096) == 4096 * 4097 / 2
    assert cost.attended_pairs(c, "sliding_attention", 300) == 300 * 301 / 2
    p = cost.prefill_flops(c, [8192]) - 2 * cost.prefill_flops(c, [4096])
    grow = lambda kind: cost.attended_pairs(c, kind, 8192) - 2 * cost.attended_pairs(c, kind, 4096)
    assert p == pytest.approx(
        2.0 * 256 * (2 * 48 * grow("full_attention") + 6 * 72 * grow("sliding_attention")) - 2.0 * 308_281_344)
    # Experts: of the 32 held, those that one row's top-10 of 256 reaches.
    assert costs.expected_experts_touched(256, 10, 1, 32) == pytest.approx(1.25)


def test_catalog_keys_kept_or_listed_as_reduced():
    # The catalog's row as ISSUE 32 drew it, kept beside this file: a test
    # reads nothing outside its checkout.
    with open(os.path.join(os.path.dirname(__file__), "laguna_s_2_1_catalog_row.json")) as f:
        row = json.load(f)
    assert row["name"] == "Laguna-S-2.1"
    config = harness.load_json(CONFIG)
    assert config["source"] == row["source_url"]
    lists = ["gating_types", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"]
    assert sorted(config["reduced"]) == sorted(lists + ["max_position_embeddings", "num_experts", "num_hidden_layers"])
    for key, value in row["config"].items():
        if key in lists:
            assert config[key] == value[:8] and "->" in config["reduced"][key]
        elif key in config["reduced"]:
            assert config[key] != value and "->" in config["reduced"][key]
        else:
            assert config[key] == value, key
    assert config["vocab_size"] == 100_352 and config["num_experts_published"] == 256
    assert config["num_experts"] == 32 and config["num_experts_per_tok"] == 10 and config["num_hidden_layers"] == 8
    assert config["memory"]["weights_bytes_bf16"] == 2 * config["memory"]["parameters"]
    held = config["memory"]["weights_bytes_bf16"] + config["memory"]["global_cache_bytes"] + config["memory"]["ring_bytes"]
    assert held >= 0.25 * 16e9, "weights + cache are at least a quarter of the chip"
    assert [k for k in config["assumed"] if config["assumed"][k].startswith("(")] == [
        "gate", "scoring", "no_qk_norm_no_shared_gate", "hidden_act"]
    assert "eight chips" in config["deployment"] and "32 of each layer's 256" in config["deployment"]
    tiny = harness.load_json(f"benchmarks/configs/rehearse/{FAMILY}.json")
    assert set(harness.model_keys(tiny)) == set(harness.model_keys(config)), "the rehearsal keeps every mechanism's key"


def test_the_reference_stands_alone_and_covers_every_answer():
    ref, _ = harness.family_modules(FAMILY)
    with open(ref.__file__) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+tpufw", src, re.M), "the reference imports nothing of the program"
    bench = harness.load_benchmark()
    cells = [w for w in bench["workloads"] if w["config"] == "laguna-s-2.1-8l-ep8"]
    assert [w["name"] for w in cells] == [CELL]
    for w in cells:
        config = harness.load_json(harness.config_entry(bench, w["config"])["file"])
        mix = harness.load_json(harness.traffic_path(w["traffic"]))
        assert mix["output"]["cap"] <= ref.MAX_AT and mix["rehearse"]["output"]["cap"] <= ref.MAX_AT
        assert mix["prompt"]["cap"] + mix["output"]["cap"] <= config["max_position_embeddings"]
        assert mix["prompt"]["base"] >= 4 * config["sliding_window"], "every prompt is at least four windows long"
        assert common.QUERY_BLOCK % ref.QUERY_BLOCK == 0, "the harness pads to its own block"
    names = {m["name"] for m in harness.metrics_of(bench, CELL, "per_layer")}
    assert {"window_hbm_share", "window_keys_share", "attended_keys_share", "decode_roofline_share"} <= names
    assert "state_hbm_share" not in names
    # The first token of a 2k-15k prompt is what this mix's users wait for:
    # the cell reports TTFT, what the whole window shows of the prefill
    # half, and the prefill's share of the chip's peak: the traced 6 s start
    # a lead before the 15,232-token prompt is due (arrivals at 6.0, 7.7,
    # 13.4 and 39.0 s; runners/serve.py::trace_offset), so its chunks are
    # in the trace however fast the server is.
    assert {m["name"] for m in harness.metrics_of(bench, CELL, "end_to_end")} == {
        "tokens_per_s_per_chip", "tpot_p50_ms", "ttft_p50_ms", "setup_s"}
    assert {m["name"] for m in harness.metrics_of(bench, CELL, "per_layer") if m["moves"] == "ttft_p50_ms"} == {
        "slo_good_share", "ttft_max_ms", "gen_late_max_ms", "join_wait_p50_ms", "queue_wait_p50_ms", "prefill_span_p50_ms",
        "prefill_mfu_share"}
    from benchmarks.runners import serve

    reqs = traffic.schedule(mix, 1, bench["run_seconds"], 100)
    start, anchor = serve.trace_offset(reqs, bench["run_seconds"])
    assert sum(r.t >= 0 for r in reqs) == 4 and len(anchor.prompt) == mix["prompt"]["cap"], "the longest prompt prefills in the traced stretch"
    assert [r.t for r in reqs if start <= r.t <= start + serve.TRACE_SECONDS] == [anchor.t]


def test_new_readers_report_nothing_where_there_is_nothing_to_read():
    from benchmarks.metrics import window_hbm_share, window_keys_share

    obs = {"prom0": {}, "prom1": {}, "rehearse": False, "trace": None, "device": {"kind": "TPU v5 lite"}}
    assert window_hbm_share.read(obs) is None and window_keys_share.read(obs) is None
    obs["prom1"]["tpufw_serve_window_bytes"] = 6 * 8 * 512 * 4104.0
    assert window_hbm_share.read(obs) == pytest.approx(0.63037, rel=1e-4)
    # A model without window layers: the counters are there and do not move.
    for name in ("tpufw_serve_window_key_slots_total", "tpufw_serve_window_row_key_slots_total"):
        obs["prom0"][name] = obs["prom1"][name] = 0.0
    assert window_keys_share.read(obs) is None
    obs["prom1"]["tpufw_serve_window_key_slots_total"] = 6 * (100 * 8 * 512 + 10 * 1024.0)
    obs["prom1"]["tpufw_serve_window_row_key_slots_total"] = 6 * (100 * 8 + 10) * 16384.0
    assert window_keys_share.read(obs) == pytest.approx(100 * (409600 + 10240) / (810 * 16384))
    obs["rehearse"] = True
    assert window_hbm_share.read(obs) is None


# ------------------------------------- a fault of the window, through the harness


@pytest.mark.parametrize("fault", ["whole_row", "wide_ring"])
def test_window_layers_that_attend_more_than_the_window_are_not_correct(fault):
    """The cell's rehearsal with every window layer attending its whole
    row, or a ring four windows wide (scripts/laguna_window_fault.py puts
    the fault into the serve phase of the benchmark's own launcher):
    replies well formed, nothing built in the window, and ``correct``
    false by the comparison with the reference, because every prompt is at
    least two windows long and most are more than four."""
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    proc = subprocess.run(
        [sys.executable, "scripts/laguna_window_fault.py", "--fault", fault, "--",
         "--workload", CELL, "--seed", "5", "--seconds", "4", "--trace", "0", "--rehearse-cpu"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads([ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    got = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is False and result["failed"] == 0
    assert got["requests_failed"] == got["replies_malformed"] == got["compiled_in_window"] == 0
    assert got["gap_mean"] > 0.05 and got["gap_max"] > 2.0
