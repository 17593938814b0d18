"""The olmo_hybrid family at the rehearsal widths on the CPU: its plain
reference against the program (full forward, periods scanned and
unrolled; prefill then decode through the cache; and through the serving
pools: whole-prompt prefill, prefill in chunks with a padded tail, decode
after each, the state carried in the row twin), what the program declines
for a model with per-slot state, int8 weights, a bfloat16 state and a lost
carry told apart, the cost functions against the parameter tree and the
store's leaves, the configuration file's keys and the mix.

Tolerance ``F32_TOL``: program and reference both in float32 at highest
matmul precision over eight post-norm layers differ by the order of their
sums only (the chunkwise rule in blocks of 64 against the token-by-token
one included); logits have unit scale. A state kept in bfloat16 or int8
weights are 50 to 1,000 times that."""

import dataclasses
import json
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs, harness
from benchmarks.costs import olmo_hybrid as cost
from benchmarks.reference import common
from benchmarks.weights import make_weights

FAMILY = "olmo_hybrid"
CELL = "olmoh-reason-pool"
CONFIG = "benchmarks/configs/olmo-hybrid-7b-16l.json"
F32_TOL = 3e-4
PAGE = 16


def build(seed=3, positions=256):
    keys = harness.model_keys(harness.load_json(harness.rehearse_path(FAMILY)))
    keys["max_position_embeddings"] = positions
    ref, adapter = harness.family_modules(FAMILY)
    weights = make_weights(ref.weight_specs(keys), seed)
    cls, pc = adapter.program_model(keys, {})
    assert not pc.scan_layers, "the cell serves the unrolled trunk, as the server unrolls any"
    pc32 = dataclasses.replace(pc, dtype=jnp.float32)
    return keys, ref, adapter.to_program(weights, keys), weights, cls, pc32


@pytest.fixture(scope="module")
def built():
    return build()


def scanned(built):
    """The same model with its PERIODS under ``nn.scan``: the parameters
    of the two periods stacked, so the STATE and PAGE leaves of a period's
    four layers are stacked ``[2, B, ...]`` in the pools too."""
    from tpufw.models import unstack_layer_params

    keys, ref, params, weights, cls, pc32 = built
    stacked = {k: v for k, v in params.items() if not k.startswith("layer_")}
    stacked["layers"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a), params["layer_0"], params["layer_1"])
    back = unstack_layer_params(stacked)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params), "the server's unroll gives the unrolled tree"
    return keys, ref, stacked, weights, cls, dataclasses.replace(pc32, scan_layers=True)


@partial(jax.jit, static_argnums=0)
def forward(model, params, tokens):
    """Logits [T, V] of one whole sequence, float32 at highest precision;
    one compile a model and length for every test here."""
    with jax.default_matmul_precision("highest"):
        return model.apply({"params": params}, tokens[None])[0]


@partial(jax.jit, static_argnums=0)
def cached_call(model, params, cache, tokens, at):
    """One call of ``tokens.shape[0]`` tokens from position ``at`` that
    continues from ``cache`` ({} at a row's start)."""
    with jax.default_matmul_precision("highest"):
        toks = tokens[None]
        out, new = model.apply({"params": params, **cache}, toks, positions=at + jnp.arange(toks.shape[1])[None],
                               segment_ids=jnp.ones_like(toks), mutable=["cache"])
    return out[0], {"cache": new["cache"]}


_REF_JIT = {}
#: Every sequence here is at most this long.
REF_T = 160


def ref_logits(built, tokens, at):
    """``ref.logits`` after the positions ``at`` under ONE jit for every
    call here: the sequence padded to ``REF_T`` tokens (the model is
    causal, so what follows a position cannot reach it) and every
    position answered. Eagerly the reference dispatches eight layers
    operation by operation, 3 s a call more on the CPU than compiling it
    whole, and a compile a length is 1.5 s."""
    keys, ref, _, weights, _, _ = built
    tokens, at = jnp.asarray(tokens), jnp.asarray(at)
    f = _REF_JIT.setdefault(
        json.dumps(keys, sort_keys=True), jax.jit(lambda w, t: ref.logits(w, keys, t, jnp.arange(REF_T))))
    logits, margin = f(weights, jnp.pad(tokens, (0, REF_T - tokens.shape[0])))
    return logits[at], margin[at]


def tokens_of(n, keys, seed):
    return jax.random.randint(jax.random.key(seed), (n,), 1, keys["vocab_size"])


# ------------------------------------------- reference against program


@pytest.mark.parametrize("trunk", ["unrolled", "scanned"])
def test_full_forward_agrees(built, trunk):
    keys, ref, params, weights, cls, pc32 = built if trunk == "unrolled" else scanned(built)
    tokens = tokens_of(96, keys, 1)
    want, margin = ref_logits(built, tokens, jnp.arange(96))
    got = forward(cls(pc32), params, tokens)
    assert 0.5 < float(jnp.std(want)) < 2.0, "seeded weights give logits of unit scale"
    assert margin.shape == (96,) and bool(jnp.all(jnp.isinf(margin))), "a dense model routes nothing"
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


def through_the_cache(model, params, tokens, n_prompt, chunk=None):
    """Logits [T, V] of prefill (whole, or in calls of ``chunk``) and then
    one-token steps, each call continuing from the cache of the last."""
    outs, cache, at = [], {}, 0
    while at < tokens.shape[0]:
        n = min(chunk or n_prompt, n_prompt - at) if at < n_prompt else 1
        logits, cache = cached_call(model, params, cache, tokens[at:at + n], at)
        outs.append(logits)
        at += n
    return jnp.concatenate(outs)


@pytest.mark.parametrize("n_prompt,chunk", [(70, None), (150, 64)])
def test_prefill_then_decode_through_the_cache_agrees(built, n_prompt, chunk):
    """A prompt that ends inside a block of the chunkwise rule, whole, and
    one in chunks whose boundaries lie on a block's edge (off it: the
    pools' cases below); the decode steps then continue from its state,
    the convolution's tail and the keys. Logits, at every position."""
    keys, ref, params, weights, cls, pc32 = built
    tokens = tokens_of(n_prompt + 10, keys, 2)
    want, _ = ref_logits(built, tokens, jnp.arange(n_prompt + 10))
    got = through_the_cache(cls(pc32.decode_config()), params, tokens, n_prompt, chunk)
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


def test_int8_weights_are_told_apart(built):
    keys, ref, params, weights, cls, pc32 = built
    tokens = tokens_of(96, keys, 4)
    want, _ = ref_logits(built, tokens, jnp.arange(96))
    rounded = {
        k: v if any(s in k for s in ref.INT8_KEEP) else common.int8_round_trip(v, v.ndim - 2)
        for k, v in weights.items()
    }
    _, adapter = harness.family_modules(FAMILY)
    got = forward(cls(pc32), adapter.to_program(rounded, keys), tokens)
    assert float(jnp.max(jnp.abs(got - want))) > 50 * F32_TOL


def test_the_programs_int8_path_knows_every_projection(built):
    """``--control int8_weights``: every kernel a ``projection`` declares
    is in the quantizer's table (one it left in bfloat16 would fail the
    quantized module's init), and nothing else is touched."""
    from tpufw.ops.quant import quantize_params

    keys, _, params, _, cls, pc32 = built
    q = quantize_params(params)
    gdn, attn = q["layer_0"]["linear_0"]["gdn"], q["layer_1"]["full_3"]["attn"]
    for name in ("q", "k", "v", "o", "gate", "decay", "beta"):
        assert set(gdn[name]) == {"q_kernel", "scale"}, name
    assert gdn["q_conv"].dtype == jnp.bfloat16 and gdn["A_log"].dtype == jnp.float32
    assert set(attn["q"]) == {"q_kernel", "scale"} and set(attn["q_norm"]) == {"scale"}
    tokens = tokens_of(24, keys, 4)
    out = forward(cls(dataclasses.replace(pc32, quantized_weights=True)), q, tokens)
    assert out.shape == (24, keys["vocab_size"]) and bool(jnp.all(jnp.isfinite(out)))


def test_a_bfloat16_state_and_a_lost_carry_are_told_apart(built, monkeypatch):
    """The recurrent state kept in bfloat16 between calls, where the
    configuration states float32; and (``scripts/solar_state_fault.py``'s
    row for this family) a state lost at every chunk boundary."""
    import sys

    from tpufw.models import olmo_hybrid

    keys, ref, params, weights, cls, pc32 = built
    tokens = tokens_of(150, keys, 5)
    want, _ = ref_logits(built, tokens, jnp.arange(150))
    # (Another model instance than the sound tests': the jitted calls are
    # traced anew, under the fault.)
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=255))
    monkeypatch.setattr(olmo_hybrid, "GDN_STATE_DTYPE", jnp.bfloat16)
    got = through_the_cache(model, params, tokens[:104], 64)
    assert float(jnp.max(jnp.abs(got[-1] - want[103]))) > 10 * F32_TOL
    monkeypatch.undo()
    sys.path.insert(0, os.path.join(harness.ROOT, "scripts"))
    try:
        import solar_state_fault
    finally:
        sys.path.pop(0)
    monkeypatch.setattr(olmo_hybrid, "kda_chunk", olmo_hybrid.kda_chunk)
    solar_state_fault.break_program("zero_carry", FAMILY)
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=254))
    got = through_the_cache(model, params, tokens[:128], 128, chunk=64)
    assert float(jnp.max(jnp.abs(got[:64] - want[:64]))) < F32_TOL, "the first chunk starts from zero anyway"
    assert float(jnp.max(jnp.abs(got[64:] - want[64:128]))) > 100 * F32_TOL


def test_decays_write_strengths_and_softmax_are_not_degenerate(built):
    """Seeded weights, at the residual stream's variance in the FIRST and
    the LAST of sixteen layers (1 and 31): the decays alpha span forgetting
    in a few tokens to remembering hundreds, beta passes 1 (negative
    eigenvalues) and the gate's pre-activation has unit scale, so a wrong
    state moves the logits; at plain fan-in the last layer forgets at
    once. QK-normed scores are neither uniform nor one-hot."""
    keys, ref, _, weights, _, _ = built
    t = 128
    p = "layers.0.gdn."
    for variance in (1.0, 31.0):
        x = jax.random.normal(jax.random.key(0), (t, keys["hidden_size"])) * variance ** 0.5
        q, k, v, g, beta = ref.delta_inputs(weights, p, keys, x)
        alpha = jnp.exp(g)
        assert bool(jnp.all((alpha > 0) & (alpha <= 1)))
        assert float(jnp.max(alpha)) > 0.995 and 0.5 < float(jnp.median(alpha)) < 0.999
        assert float(jnp.max(beta)) > 1.2 and float(jnp.min(beta)) < 0.8
        np.testing.assert_allclose(np.asarray(jnp.sum(k * k, -1)), 1.0, atol=1e-3)
        wiped = float(jnp.mean(alpha < 0.5))
        assert wiped < 0.12, "a head's state is not wiped every few tokens"
    plain = {**weights, p + "decay": (weights[p + "decay"].astype(jnp.float32) * ref.RESIDUAL_VARIANCE ** 0.5).astype(jnp.bfloat16)}
    assert float(jnp.mean(jnp.exp(ref.delta_inputs(plain, p, keys, x)[3]) < 0.5)) > 2 * max(wiped, 0.05)
    pa = "layers.3."
    h, hd = keys["num_attention_heads"], ref.head_dim(keys)
    qn = common.rms_norm(common.mm(x, weights[pa + "q_proj"]), weights[pa + "q_norm"], 1e-6).reshape(t, h, hd)
    kn = common.rms_norm(common.mm(x, weights[pa + "k_proj"]), weights[pa + "k_norm"], 1e-6).reshape(t, h, hd)
    top = jnp.max(jax.nn.softmax(jnp.einsum("hd,khd->hk", qn[-1], kn) * hd ** -0.5, axis=-1), axis=-1)
    assert 2.0 / t < float(jnp.median(top)) < 0.9


def test_state_stays_finite_over_8192_positions(built):
    """The seeded decays over twice the longest context the cell admits:
    the reference's token-by-token rule neither overflows nor dies."""
    keys, ref, _, weights, _, _ = built
    x = jax.random.normal(jax.random.key(2), (8192, keys["hidden_size"])) * 4.0
    with jax.default_matmul_precision("highest"):
        o = jax.jit(lambda x: ref.recurrence(*ref.delta_inputs(weights, "layers.1.gdn.", keys, x)))(x)
    assert bool(jnp.all(jnp.isfinite(o))) and 1e-3 < float(jnp.std(o[-512:])) < 1e2


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


@partial(jax.jit, static_argnums=0)
def _peek(model, params, cache, token, pos):
    from tpufw.infer.generate import _model_apply

    apply = _model_apply(model, params)
    return apply(cache, token[:, None], pos[:, None], jnp.ones((token.shape[0], 1), jnp.int32))[0][:, -1]


def peek(pool):
    """Next-token logits [slots, V] out of the pool's own cache: what its
    decode step computes before it samples (nothing is donated; one
    compile a model for every call here)."""
    with jax.default_matmul_precision("highest"):
        return _peek(pool.model, pool.params, pool.cache, pool.token, pool.pos)


def admit_whole(pool, slot, prompt, budget):
    from tpufw.infer import slots

    ids, shared = pool.acquire_pages(prompt, len(prompt) + budget)
    assert shared == 0
    with jax.default_matmul_precision("highest"):
        cache, _, first, _, seen = slots.prefill_row(
            pool.row_model, pool.params, prompt, jax.random.key(0),
            sampling=pool.sampling, eos_id=None, pad_to=len(prompt),
        )
        pool.insert_paged(slot, cache, first, len(prompt), budget, ids, 0, row_seen=seen)
    return first


def admit_chunked(pool, slot, prompt, budget, chunk_pages):
    with jax.default_matmul_precision("highest"):
        cp = pool.start_chunked(prompt, len(prompt) + budget, jax.random.key(0), chunk_pages)
        while pool.chunk_step(cp) != "done":
            pass
        pool.finalize_chunked(slot, cp, budget)
    return cp


def check_row(built, pool, slot, prompt, first, n_steps=6):
    """The pool's LOGITS for ``slot`` agree with the reference's after the
    prompt and again after ``n_steps`` decode steps through the pool."""
    keys, ref, _, weights, _, _ = built
    seq = list(prompt) + [first]
    want = ref_logits(built, seq, [len(prompt) - 1, len(prompt)])[0]
    assert int(jnp.argmax(want[0])) == first, "the prefill sampled the reference's first token"
    assert float(jnp.max(jnp.abs(peek(pool)[slot] - want[1]))) < F32_TOL
    with jax.default_matmul_precision("highest"):
        out = np.asarray(pool.decode_steps(jax.random.split(jax.random.key(1), n_steps)))[slot]
    seq = seq + out.tolist()
    want = ref_logits(built, seq, [len(seq) - 1])[0]
    assert float(jnp.max(jnp.abs(peek(pool)[slot] - want[0]))) < F32_TOL


@pytest.mark.parametrize("path,n_prompt,chunk_pages", [
    ("whole", 70, 0),            # paged rows prefill at their exact width
    ("chunked", 150, 4),         # 64, 64 and 22 padded to 32: boundaries on a block's edge
    ("chunked_scanned", 100, 3),  # 48, 48, 4 padded to 16: inside a block; STATE and PAGE leaves stacked [2, B, ...]
])
def test_prefill_through_the_pools_then_decode_agrees(built, path, n_prompt, chunk_pages):
    """The state, the convolution's tail and the keys ride in the row twin
    from chunk to chunk, do not move on a padded tail, and arrive whole at
    the insert."""
    if path == "chunked_scanned":
        built = scanned(built)
    keys = built[0]
    prompt = tokens_of(n_prompt, keys, 12).tolist()
    pool = pool_of(built)
    if path == "whole":
        check_row(built, pool, 1, prompt, admit_whole(pool, 1, prompt, 16))
        return
    cp = admit_chunked(pool, 2, prompt, 16, chunk_pages)
    assert cp.n_chunks == -(-n_prompt // (chunk_pages * PAGE))
    if path == "chunked_scanned":
        period = pool.cache["cache"]["layers"]
        assert period["linear_0"]["gdn"]["gdn_state"].shape == (2, 3, 4, 12, 20), "[periods, slots, heads, d_k, d_v]"
        assert period["linear_2"]["gdn"]["conv_state"].shape == (2, 3, 3, 2 * 48 + 80)
        assert period["full_3"]["attn"]["cached_key"].shape[0] == 2 and "gdn" not in period["full_3"]
        assert period["full_3"]["attn"]["cached_key"].shape[-2:] == (8, 16), "4 heads and 4 of zeros: a whole tile"
    check_row(built, pool, 2, prompt, cp.first_int)


def test_the_scheduler_declines_by_the_stores_rule_and_counts_the_state_it_moves(built):
    """Prefix reuse, export and speculation are declined as ``state_layers``
    (``kv_store.DECLINES``, no reason of this family's own); the host's
    count of state bytes is the program's shapes."""
    from tpufw.infer import SamplingConfig
    from tpufw.ops import kv_store
    from tpufw.workloads import serve

    keys, ref, params, weights, cls, pc32 = built
    assert kv_store.role("gdn_state") == kv_store.Role(kv_store.STATE, 4)
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=256))
    metrics = serve._Metrics()
    sched = serve._SlotScheduler(
        model, params, eos_id=None, default_sampling=SamplingConfig(temperature=0.0),
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=True,
        prefill_chunk_pages=2, metrics=metrics,
    )
    shared = tokens_of(64, keys, 31).tolist()
    prompts = [shared + tokens_of(8, keys, 32 + i).tolist() for i in range(2)]
    with jax.default_matmul_precision("highest"):
        outs = [sched.submit([p], 5)[0][0] for p in prompts]
    reg = metrics.registry
    assert reg.counter("tpufw_serve_prefix_declined_total").value(reason="state_layers") == 2
    pool = sched._pool
    assert pool.prefix is None and pool.prefix_decline == "state_layers"
    state = pool.cache["cache"]["layer_0"]["linear_1"]["gdn"]["gdn_state"]
    assert state.shape == (sched.n_slots, 4, 12, 20) and state.dtype == jnp.float32
    a_slot = cost.state_bytes_per_row({**keys, "num_hidden_layers": 8}, bytes_per=4)
    assert a_slot == 6 * (4 * 12 * 20 * 4 + 3 * (2 * 48 + 80) * 4)
    assert reg.gauge("tpufw_serve_state_bytes").value() == pool.state_bytes == sched.n_slots * a_slot
    assert reg.counter("tpufw_serve_state_live_bytes_total").value() > 0
    with pytest.raises(ValueError, match=r"export_slot: OlmoHybrid keeps per-slot state"):
        pool.export_slot(0)
    with pytest.raises(ValueError, match=r"speculative decoding: OlmoHybrid"):
        pool.spec_steps(np.zeros((sched.n_slots, 2), np.int32), jax.random.key(0))
    # The second answer is the reference's greedy continuation of ITS prompt.
    seq = prompts[1] + outs[1]
    want, _ = ref_logits(built, seq[:-1], jnp.arange(len(prompts[1]) - 1, len(seq) - 1))
    served = want[jnp.arange(5), jnp.asarray(outs[1])]
    assert float(jnp.max(jnp.max(want, axis=-1) - served)) < 1e-3


def test_no_file_of_the_pools_or_the_server_names_the_family():
    import pathlib

    import tpufw

    root = pathlib.Path(tpufw.__file__).parent
    for source in [*(root / "infer").glob("*.py"), root / "workloads" / "serve.py"]:
        text = source.read_text().lower()
        for spelled in ("olmo", "gdn_", "deltanet"):
            assert spelled not in text, (source.name, spelled)
    text = (root / "ops" / "kda.py").read_text()
    assert text.count("solve_triangular(") == 1, "one triangular solve for both shapes of decay"


# ------------------------------------------------- costs and configuration


def real_keys():
    return harness.model_keys(harness.load_json(CONFIG))


def test_cost_functions_count_the_parameter_tree_and_the_stores_leaves(built):
    """At the rehearsal widths, against what the program really holds."""
    keys, _, params, _, cls, pc32 = built
    c = {**keys, "num_hidden_layers": 8}
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert cost.parameters(c) == n == pc32.n_params()
    pool = pool_of(built, n_slots=2)
    per_slot = page_bytes = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(pool.cache):
        from tpufw.ops import kv_store

        role = kv_store.path_role(path)
        if role.kind == kv_store.STATE:
            per_slot += leaf.nbytes // 2
        elif role.kind == kv_store.PAGE:
            page_bytes += leaf.nbytes // (leaf.shape[0] * leaf.shape[1])
    assert cost.state_bytes_per_row(c, bytes_per=4) == per_slot, "float32 activations here"
    # The cost function counts the model's 4 heads; a page holds a whole
    # tile of 8 (``kv_store_heads``, derived), as the tiling would pad them anyway.
    assert pc32.kv_store_heads == 8 and pc32.n_kv_heads == 4
    assert dataclasses.replace(pc32, n_kv_heads=30).kv_store_heads == 32
    assert cost.cache_bytes_per_token(c, bytes_per=4) * 8 // 4 == page_bytes


def test_cost_goldens():
    """ISSUE 41's reckoning, redone by the cost functions."""
    c = real_keys()
    p = cost.layer_params(c)
    assert (p["full"], p["linear"], p["mlp"]) == (58_990_080, 88_750_332, 126_812_160)
    assert cost.layer_total(c, "linear_attention") == 215_570_172 and cost.layer_total(c, "full_attention") == 185_809_920
    assert p["embed"] + p["head"] == 770_703_360
    memory = harness.load_json(CONFIG)["memory"]
    assert cost.parameters(c) == 4_100_788_944 == memory["parameters"]
    full = {**c, "num_hidden_layers": 32, "layer_types": c["layer_types"] * 2}
    assert cost.parameters(full) == 7_430_870_688
    assert cost.cache_bytes_per_token(c) == costs.cache_bytes_per_token(FAMILY, c) == 61_440 == memory["cache_bytes_per_token"]
    assert cost.state_bytes_per_row(c) == 27_371_520 == memory["state_bytes_per_slot"]
    assert costs.decode_step_bytes(FAMILY, c, 0, []) == 7_430_874_528.0
    assert costs.decode_step_bytes(FAMILY, c, 12, [1024] * 12) == 7_430_874_528.0 + 12 * (2 * 3840 + 1024 * 61_440 + 2 * 27_371_520)
    assert cost.gdn_step_bytes(c, 16) == 16 * 12 * (2 * 30 * 96 * 192 * 4 + (2 * 2880 + 2 * 5760) * 2 + 2 * 30 * 4)
    assert cost.gdn_chunk_bytes(c, 512) == 12 * (2 * 30 * 96 * 192 * 4 + 512 * ((2 * 2880 + 2 * 5760) * 2 + 240))
    assert cost.gdn_chunk_flops(c, 512) == 512 * 12 * 30 * (6.0 * 96 * 192 + 4.0 * 96 * 32.5 + 63.0 * 192 + 2.0 * 192 * 32.5)
    assert cost.gdn_chunk_flops(c, 1024) == 2 * cost.gdn_chunk_flops(c, 512)
    head = p["head"]
    whole = costs.prefill_flops(FAMILY, c, [512])
    assert whole == pytest.approx(2.0 * (cost.active_matmul_params(c) - head) * 512 + cost.gdn_chunk_flops(c, 512) + 2.0 * head
                                  + 2.0 * 30 * 256 * 4 * 512 * 513 / 2)
    assert costs.prefill_chunk_flops(FAMILY, c, 512, [512]) == pytest.approx(whole - 2.0 * head)


def test_state_counts_by_the_row_and_keys_by_the_token_in_the_full_layers_alone():
    c = real_keys()
    one, long = cost.decode_step_bytes(c, [100]), cost.decode_step_bytes(c, [2100])
    assert long - one == 2000 * 61_440, "K/V grow by the token, in four layers of sixteen"
    none = cost.decode_step_bytes(c, [])
    assert one - none == 2 * 3840 + 100 * 61_440 + 2 * 27_371_520
    head = 2 * cost.layer_params(c)["head"]
    assert 0.103 < head / none < 0.105
    full = {**c, "num_hidden_layers": 32, "layer_types": c["layer_types"] * 2}
    assert 0.054 < head / cost.decode_step_bytes(full, []) < 0.056
    # The memory the configuration reckons: 16 slots x 4,096 (and a whole tile of 32 heads a page in HBM).
    assert 16 * 4096 * 61_440 == 4_026_531_840 and 16 * 27_371_520 == 437_944_320
    assert 16 * 4096 * 61_440 * 32 // 30 == 4_294_967_296


def test_catalog_keys_kept_or_listed_as_reduced():
    # The catalog's row as ISSUE 41 drew it, kept beside this file: a test
    # reads nothing outside its checkout.
    with open(os.path.join(os.path.dirname(__file__), "olmo_hybrid_catalog_row.json")) as f:
        row = json.load(f)
    assert row["name"] == "Olmo-Hybrid-7B"
    config = harness.load_json(CONFIG)
    entry = harness.config_entry(harness.load_benchmark(), "olmo-hybrid-7b-16l")
    assert config["source"] == row["source_url"] == entry["source"] and len(entry["source"]) <= 200
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == ["layer_types", "max_position_embeddings", "num_hidden_layers"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value and "->" in config["reduced"][key] or key == "layer_types"
        else:
            assert config[key] == value, key
    assert config["layer_types"] == row["config"]["layer_types"][:16] == (["linear_attention"] * 3 + ["full_attention"]) * 4
    assert (config["num_hidden_layers"], config["max_position_embeddings"]) == (16, 4096)
    assert config["memory"]["weights_bytes_bf16"] == 2 * config["memory"]["parameters"]
    for k in ("rotary", "norms", "block", "linear_layer", "decay", "dtype", "weights"):
        assert k in config["assumed"]
    assert "two pipeline stages" in config["deployment"]
    for k in ("logit_noise", "gap_max", "gap_mean", "why"):
        assert config["check"][k]


def test_the_reference_stands_alone_and_covers_every_answer():
    ref, _ = harness.family_modules(FAMILY)
    with open(ref.__file__) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+tpufw", src, re.M), "the reference imports nothing of the program"
    bench = harness.load_benchmark()
    cells = [w for w in bench["workloads"] if harness.load_json(harness.config_entry(bench, w["config"])["file"])["family"] == FAMILY]
    cells = [w for w in cells if w["name"] == CELL]
    assert len(cells) == 1 and cells[0]["chips"] == 1 and len(cells[0]["why"]) <= 200
    config = harness.load_json(CONFIG)
    mix = harness.load_json(harness.traffic_path(cells[0]["traffic"]))
    assert mix["output"]["cap"] <= ref.MAX_AT and mix["rehearse"]["output"]["cap"] <= ref.MAX_AT
    assert mix["prompt"]["cap"] + mix["output"]["cap"] == config["max_position_embeddings"]
    assert config["vocab_size"] % ref.HEAD_BLOCK == 0 and ref.MAX_AT % ref.AT_BLOCK == 0, "the head in whole blocks"
    assert harness.missing_parts(bench, cells[0], config) == []


def test_the_head_in_blocks_answers_the_last_positions(built):
    """Blocks of answer positions by blocks of vocabulary columns give
    what one product gives, at the LAST positions as at the first."""
    ref = built[1]
    h = jax.random.normal(jax.random.key(0), (4 * ref.AT_BLOCK, 8))
    w = jax.random.normal(jax.random.key(1), (8, 2 * ref.HEAD_BLOCK)).astype(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(ref.head)(h, w), common.mm(h, w)
    np.testing.assert_allclose(np.asarray(got[-3:]), np.asarray(want[-3:]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[:3]), np.asarray(want[:3]), atol=1e-5)


def test_the_mix_is_issue_41s_and_its_traced_stretch_holds_prefill_beside_a_live_pool():
    from benchmarks import traffic
    from benchmarks.runners import serve as runner

    mix = harness.load_json(harness.traffic_path("reason-pool"))
    assert mix["prompt"] == {**mix["prompt"], "base": 128, "alpha": 1.0, "cap": 2048, "quantum": 64}
    assert mix["output"] == {**mix["output"], "base": 256, "alpha": 1.0, "cap": 2048}
    arr = mix["arrivals"]
    assert arr["process"] == "poisson" and (mix["ramp_s"], mix["drain_s"], mix["shape_seed"]) == (30, 20, 0)
    assert mix["server_env"] == {"TPUFW_SERVE_SLOTS": 16, "TPUFW_SERVE_PAGE": 16, "TPUFW_SERVE_PREFILL_CHUNK": 32,
                                 "TPUFW_SERVE_CHUNK": 8, "TPUFW_SERVE_CACHE_FLOOR": 4096}
    # 0.8 x the knee, rounded down to a whole number of requests a window.
    assert arr["rate_rps"] == pytest.approx(int(0.8 * mix["knee"]["knee_rps"] * 45 + 1e-9) / 45.0, abs=6e-4)
    reqs = traffic.schedule(mix, 1, 45.0, 100_352)
    in_win = [r for r in reqs if r.t >= 0]
    assert len(in_win) == round(arr["rate_rps"] * 45)
    lens = sorted(len(r.prompt) for r in in_win)
    assert lens[0] == 128 and lens[-1] == 2048 and lens[len(lens) // 2] in (256, 320)
    outs = sorted(r.max_new for r in in_win)
    assert outs[0] >= 256 and outs[-1] == 2048 and 480 <= outs[len(outs) // 2] <= 560
    # The traced 6 s start at an arrival; rows admitted in the half minute before it still decode beside its chunks.
    offset, anchor = runner.trace_offset(reqs, 45.0)
    assert anchor is not None
    inside = [r for r in reqs if offset <= r.t <= offset + runner.TRACE_SECONDS]
    before = [r for r in reqs if offset - 30.0 <= r.t < offset and r.max_new >= 512]
    assert len(inside) >= 3 and sum(len(r.prompt) for r in inside) >= 1024 and len(before) >= 4


def test_the_chunk_readers_read_this_familys_chunks():
    """``prefill_mfu_share.tpot`` and ``prefill_dev_ms_per_ktok`` over what
    the cell's first traced run held (PERF.md section 5: seven chunks,
    3,008 tokens in 0.2934 s of device time, prompts of 704, 2,048 and 256
    prefilling in the stretch): the readers take the family's cost
    function by name and the share stays under the peak."""
    import importlib

    config = harness.load_json(CONFIG)
    obs = {"trace": {"programs": {"jit__prefill_chunk_jit": {"seconds": 0.293419614, "tokens": 3008}}},
           "family": FAMILY, "config": config, "device": {"kind": "TPU v5 lite"}, "t0": 0.0, "seconds": 45.0,
           "traced_from": 10.79, "traced_s": 6.0, "records": [
               {"due": 11.04, "n_prompt": 704, "chunks": [(11.2, 1)], "done": None},
               {"due": 12.32, "n_prompt": 2048, "chunks": [(12.9, 1)], "done": None},
               {"due": 16.06, "n_prompt": 256, "chunks": [(16.2, 1)], "done": None},
               {"due": 2.0, "n_prompt": 512, "chunks": [(2.2, 1), (20.0, 600)], "done": 20.0}]}
    read = lambda name: importlib.import_module(harness.reader_module(name)).read(obs)
    assert read("prefill_dev_ms_per_ktok") == pytest.approx(97.546, abs=1e-3)
    need = costs.prefill_chunk_flops(FAMILY, config, 3008, [704, 2048, 256])
    assert read("prefill_mfu_share.tpot") == pytest.approx(100 * need / (0.293419614 * 197e12)) == pytest.approx(35.18, abs=0.01)
    obs["trace"]["programs"]["jit__prefill_chunk_jit"]["widths_unread"] = 1
    assert read("prefill_mfu_share.tpot") is None and read("prefill_dev_ms_per_ktok") is None


def test_the_cell_reports_the_state_and_first_token_metrics():
    per_layer = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    # ``in``, not a position or a whole list: the next cell is appended to
    # these lists by a PR that may not edit this file.
    for name in ("state_hbm_share", "state_live_share", "attended_keys_share", "first_token_p50_ms", "gen_late_max_ms.tokens", "prefill_dev_ms_per_ktok",
                 "prefill_mfu_share.tpot", "slo_good_share.tpot", "ttft_max_ms.tpot", "join_wait_p50_ms.tpot",
                 "queue_wait_p50_ms.tpot", "prefill_span_p50_ms.tpot"):
        assert CELL in per_layer[name]["workloads"], name
    # Nothing to read here: the cell holds no ``ttft_p50_ms`` end to end and has no window layer.
    for name in ("slo_good_share", "ttft_max_ms", "prefill_mfu_share", "window_keys_share", "window_hbm_share"):
        assert CELL not in per_layer[name]["workloads"], name
