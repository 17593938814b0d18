"""The Phi-4-mini-flash family at tiny widths on the CPU, seeded: each
mixer against the plain reference's (the selective scan in chunks with a
carried state, its one-token step, the Gated Memory Unit, differential
attention in the program's grouped form against the paired form, a window
against the whole row), prefill in chunks then decode through the paged
pool against the reference's full forward, and what the store holds for
it: ONE page pair that the cross layers read and do not declare, a slot
that keeps nothing of the row before it, and pages past a row's cursor
that reach no reader.

One built model for the module. Tolerance ``TOL``: program and reference
both in float32 at highest matmul precision differ by the order of their
sums only; logits and mixer outputs have unit scale."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import common
from benchmarks.weights import make_weights
from tpufw.models import phi4flash
from tpufw.ops import kv_store, mamba

FAMILY = "phi4flash"
TOL = 2e-4
PAGE = 16
#: Every sequence here is at most this long (the reference's one compile).
REF_T = 512


@pytest.fixture(scope="module")
def built():
    keys = harness.model_keys(harness.load_json(harness.rehearse_path(FAMILY)))
    ref, adapter = harness.family_modules(FAMILY)
    weights = make_weights(ref.weight_specs(keys), 3)
    cls, pc = adapter.program_model(keys, {})
    pc32 = dataclasses.replace(pc, dtype=jnp.float32)
    fwd = jax.jit(lambda w, t: ref.logits(w, keys, t, jnp.arange(REF_T))[0])
    return keys, ref, adapter.to_program(weights, keys), weights, cls, pc32, fwd


def ref_logits(built, tokens, at):
    tokens = jnp.asarray(tokens)
    return built[6](built[3], jnp.pad(tokens, (0, REF_T - tokens.shape[0])))[jnp.asarray(at)]


def tokens_of(n, keys, seed):
    return jax.random.randint(jax.random.key(seed), (n,), 1, keys["vocab_size"])


def hi(f, *a, **k):
    with jax.default_matmul_precision("highest"):
        return f(*a, **k)


def apply(module, tree, *args, **kw):
    return hi(module.apply, {"params": tree}, *args, **kw)


def close(got, want, tol=TOL):
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want)))) < tol


# ------------------------------------------------------------- the mixers


def scan_inputs(t=40, d=24, n=4, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (2, t, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, t, d)) - 2.0)
    a_neg = -jnp.exp(jax.random.normal(ks[2], (n, d)))
    b_in, c_in = jax.random.normal(ks[3], (2, t, n)), jax.random.normal(ks[4], (2, t, n))
    return x, dt, a_neg, b_in, c_in, jnp.linspace(0.5, 1.5, d)


def token_by_token(x, dt, a_neg, b_in, c_in, d_skip):
    """The recurrence as the reference writes it, a row at a time, state
    [D, N]."""
    def row(x, dt, b_in, c_in):
        def step(s, xs):
            x_t, dt_t, b_t, c_t = xs
            s = jnp.exp(dt_t[:, None] * a_neg.T) * s + (dt_t * x_t)[:, None] * b_t[None, :]
            return s, s @ c_t + d_skip * x_t
        return jax.lax.scan(step, jnp.zeros(a_neg.T.shape), (x, dt, b_in, c_in))
    return jax.vmap(row)(x, dt, b_in, c_in)


def test_scan_in_chunks_with_a_carried_state_is_the_token_by_token_rule():
    x, dt, a_neg, b_in, c_in, d_skip = scan_inputs()
    s_want, y_want = token_by_token(x, dt, a_neg, b_in, c_in, d_skip)
    cut = lambda a, lo, hi_: a[:, lo:hi_]
    state, ys = jnp.zeros((2, 4, 24)), []
    for lo, hi_ in ((0, 16), (16, 29), (29, 40)):
        y, state = mamba.selective_chunk(
            cut(x, lo, hi_), cut(dt, lo, hi_), a_neg, cut(b_in, lo, hi_), cut(c_in, lo, hi_), d_skip, state)
        ys.append(y)
    assert close(jnp.concatenate(ys, 1), y_want, 1e-5)
    assert close(jnp.swapaxes(state, 1, 2), s_want, 1e-5)


def test_a_decode_step_is_the_chunks_last_token_and_padding_is_the_identity():
    x, dt, a_neg, b_in, c_in, d_skip = scan_inputs(t=9)
    y_all, s_all = mamba.selective_chunk(x, dt, a_neg, b_in, c_in, d_skip, jnp.zeros((2, 4, 24)))
    _, s8 = mamba.selective_chunk(x[:, :8], dt[:, :8], a_neg, b_in[:, :8], c_in[:, :8], d_skip, jnp.zeros((2, 4, 24)))
    y9, s9 = mamba.selective_step(x[:, 8], dt[:, 8], a_neg, b_in[:, 8], c_in[:, 8], d_skip, s8)
    assert close(y9, y_all[:, 8], 1e-5) and close(s9, s_all, 1e-5)
    # Positions that are not valid leave the state where it was (decay 1,
    # nothing written), whatever they hold.
    valid = jnp.arange(9)[None, :] < jnp.asarray([[8], [9]])
    _, s_pad = mamba.selective_chunk(x.at[0, 8].set(1e4), dt, a_neg, b_in, c_in, d_skip, jnp.zeros((2, 4, 24)), valid)
    assert close(s_pad[0], s8[0], 1e-6) and close(s_pad[1], s_all[1], 1e-6)


def layer_tree(built, i):
    """(the program's parameters of layer ``i``'s block, its reference
    prefix, its kind)."""
    keys, ref, params, *_ = built
    kinds = ref.layer_kinds(keys)
    half = len(kinds) // 2
    if i < half:
        tree = params[f"self_layer_{i // 2}"][kinds[i]]
    elif i >= half + 2:
        tree = params[f"cross_layer_{(i - half - 2) // 2}"][kinds[i]]
    else:
        tree = params[kinds[i]]
    return tree, f"layers.{i}.", kinds[i]


def test_mamba_mixer_is_the_references_and_hands_on_the_scan_before_the_gate(built):
    keys, ref, _, weights, _, pc32, _ = built
    tree, p, kind = layer_tree(built, 4)
    assert kind == "memory"
    u = jax.random.normal(jax.random.key(1), (1, 48, keys["hidden_size"]))
    out, y = apply(phi4flash.MambaMixer(pc32), tree["mamba"], u)
    with jax.default_matmul_precision("highest"):
        want, y_want = ref.mamba(weights, p + "mamba.", keys, u[0])
    assert 0.2 < float(jnp.std(want)) < 5.0
    assert close(out[0], want) and close(y[0], y_want)
    assert y.shape == (1, 48, 2 * keys["hidden_size"]) and y.dtype == jnp.float32


def test_gated_memory_unit_is_the_references(built):
    keys, ref, _, weights, _, pc32, _ = built
    tree, p, kind = layer_tree(built, 6)
    assert kind == "gmu"
    u = jax.random.normal(jax.random.key(2), (1, 20, keys["hidden_size"]))
    memory = jax.random.normal(jax.random.key(3), (1, 20, 2 * keys["hidden_size"]))
    got = apply(phi4flash.GatedMemoryUnit(pc32), tree["gmu"], u, memory)
    with jax.default_matmul_precision("highest"):
        want = common.mm(memory[0] * common.silu(common.mm(u[0], weights[p + "gmu.in_proj"])), weights[p + "gmu.out_proj"])
    assert close(got[0], want)


@pytest.mark.parametrize("i,kind,t", [(1, "window", 80), (5, "full", 80), (3, "window", 24)])
def test_grouped_form_of_differential_attention_is_the_paired_form(built, i, kind, t):
    """The program's 2 hd-wide stored pairs under zero-padded queries
    against the reference's two softmaxes a pair; a window of 32 under
    rows of 80, and the whole row where the row is shorter than it."""
    keys, ref, _, weights, _, pc32, _ = built
    tree, p, got_kind = layer_tree(built, i)
    assert got_kind == kind
    u = jax.random.normal(jax.random.key(4), (1, t, keys["hidden_size"]))
    out, handed = apply(phi4flash.DiffAttention(pc32, kind=kind), tree["attn"], u, None, i)
    window = keys["sliding_window"] if kind == "window" else None
    with jax.default_matmul_precision("highest"):
        want, (k, v) = ref.diff_attention(weights, p + "attn.", keys, i, u[0], window=window)
        whole, _ = ref.diff_attention(weights, p + "attn.", keys, i, u[0])
    assert 0.1 < float(jnp.std(want)) < 5.0
    assert close(out[0], want)
    assert close(want, whole) == (window is None or t <= window), "a window is the whole row while the row is shorter"
    pairs, hd = keys["num_key_value_heads"] // 2, ref.head_dim(keys)
    assert handed[0].shape == (1, t, pairs, 2 * hd), "a K/V pair is one stored head"
    assert close(handed[0][0].reshape(t, 2 * pairs, hd), k)


def test_cross_attention_reads_the_full_layers_keys_with_its_own_queries(built):
    keys, ref, _, weights, _, pc32, _ = built
    full, pf, _ = layer_tree(built, 5)
    cross, pcr, kind = layer_tree(built, 7)
    assert kind == "cross" and set(cross["attn"]) >= {"q", "o"} and "k" not in cross["attn"] and "v" not in cross["attn"]
    u5 = jax.random.normal(jax.random.key(5), (1, 40, keys["hidden_size"]))
    u7 = jax.random.normal(jax.random.key(6), (1, 40, keys["hidden_size"]))
    _, handed = apply(phi4flash.DiffAttention(pc32, kind="full"), full["attn"], u5, None, 5)
    out, _ = apply(phi4flash.DiffAttention(pc32, kind="cross"), cross["attn"], u7, None, 7, handed)
    with jax.default_matmul_precision("highest"):
        _, kv = ref.diff_attention(weights, pf + "attn.", keys, 5, u5[0])
        want, _ = ref.diff_attention(weights, pcr + "attn.", keys, 7, u7[0], kv=kv)
    assert close(out[0], want)


def test_lambda_init_follows_the_zero_based_depth(built):
    _, ref, *_ = built
    for i in (0, 1, 17, 31):
        assert abs(float(phi4flash.lambda_init(i)) - ref.lambda_init(i)) < 1e-6
    assert phi4flash.layer_kinds(32) == tuple(ref.layer_kinds({"num_hidden_layers": 32, "mb_per_layer": 2}))
    kinds = phi4flash.layer_kinds(32)
    assert kinds[16] == "memory" and kinds[17] == "full" and kinds.count("cross") == 7 and kinds.count("window") == 8
    assert phi4flash.Phi4FlashConfig().kv_page_readers == 8 == kv_store.page_readers(phi4flash.Phi4FlashConfig())
    with pytest.raises(ValueError, match="whole"):
        phi4flash.layer_kinds(6)


# ------------------------------------------------- the whole model, cached


@partial(jax.jit, static_argnums=0)
def forward(model, params, tokens):
    with jax.default_matmul_precision("highest"):
        return model.apply({"params": params}, tokens[None])[0]


def scanned(built):
    """The same model with its pairs under ``nn.scan``; the server's
    unroll gives the unrolled tree back."""
    from tpufw.models import unstack_layer_params

    keys, ref, params, weights, cls, pc32, _ = built
    stack = lambda *names: jax.tree_util.tree_map(lambda *a: jnp.stack(a), *(params[n] for n in names))
    stacked = {k: v for k, v in params.items() if "_layer_" not in k}
    stacked["self_layers"] = stack("self_layer_0", "self_layer_1")
    stacked["cross_layers"] = stack("cross_layer_0")
    back = unstack_layer_params(stacked)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    return stacked, dataclasses.replace(pc32, scan_layers=True)


@pytest.mark.parametrize("trunk", ["unrolled", "scanned"])
def test_full_forward_agrees(built, trunk):
    keys, ref, params, weights, cls, pc32, _ = built
    if trunk == "scanned":
        params, pc32 = scanned(built)
    tokens = tokens_of(96, keys, 1)
    want = ref_logits(built, tokens, jnp.arange(96))
    assert 0.5 < float(jnp.std(want)) < 2.0, "seeded weights give logits of unit scale"
    assert close(forward(cls(pc32), params, tokens), want)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == pc32.n_params()


def pool_of(built, n_slots=3, positions=256):
    from tpufw.infer import SamplingConfig, pages

    keys, ref, params, weights, cls, pc32, _ = built
    cfg = dataclasses.replace(pc32.decode_config(), max_seq_len=positions)
    paged = dataclasses.replace(cfg, kv_page=PAGE, kv_pages=n_slots * (positions // PAGE) + 1)
    return pages.PagedSlotPool.create_paged(
        cls(paged), cls(cfg), params, n_slots,
        sampling=SamplingConfig(temperature=0.0), eos_id=None, prefix_cache=True,
    )


@partial(jax.jit, static_argnums=0)
def _peek(model, params, cache, token, pos):
    from tpufw.infer.generate import _model_apply

    apply = _model_apply(model, params)
    return apply(cache, token[:, None], pos[:, None], jnp.ones((token.shape[0], 1), jnp.int32))[0][:, -1]


def peek(pool, cache=None):
    """Next-token logits [slots, V] out of the pool's own cache (or of
    ``cache`` in its place): what its decode step computes before it
    samples. Nothing is donated."""
    return hi(_peek, pool.model, pool.params, pool.cache if cache is None else cache, pool.token, pool.pos)


def admit_chunked(pool, slot, prompt, budget, chunk_pages=4):
    with jax.default_matmul_precision("highest"):
        cp = pool.start_chunked(prompt, len(prompt) + budget, jax.random.key(0), chunk_pages)
        while pool.chunk_step(cp) != "done":
            pass
        pool.finalize_chunked(slot, cp, budget)
    return cp


def decode(pool, n, seed=1):
    return np.asarray(hi(pool.decode_steps, jax.random.split(jax.random.key(seed), n)))


@pytest.fixture(scope="module")
def served(built):
    """A pool with a row in slot 2: a prompt of 150 tokens through chunks
    of 64 (a padded tail), then 40 decode steps: a row of 190 tokens
    under a window of 32. (pool, the row's tokens so far.)"""
    keys = built[0]
    prompt = tokens_of(150, keys, 12).tolist()
    pool = pool_of(built)
    cp = admit_chunked(pool, 2, prompt, 64)
    assert cp.n_chunks == 3
    want = ref_logits(built, prompt + [cp.first_int], [149, 150])
    assert int(jnp.argmax(want[0])) == cp.first_int, "the prefill sampled the reference's first token"
    assert close(peek(pool)[2], want[1])
    seq = prompt + [cp.first_int] + decode(pool, 40)[2].tolist()
    return pool, seq


def test_chunked_prefill_then_paged_decode_is_the_references_full_forward(built, served):
    pool, seq = served
    assert len(seq) == 191 > 150 + built[0]["sliding_window"]
    want = ref_logits(built, seq, jnp.arange(150, 191))
    # Every decoded token is the reference's greedy choice, and the logits
    # after the last agree.
    assert [int(t) for t in jnp.argmax(want[:-1], -1)] == seq[151:]
    assert close(peek(pool)[2], want[-1])


def test_the_cache_holds_one_page_pair_and_the_cross_layers_declare_nothing(built, served):
    pool, _ = served
    tree = pool.cache["cache"]
    assert set(tree) == {"self_layer_0", "self_layer_1", "memory", "full"}, "no cross layer holds a cache leaf"
    names = [kv_store.leaf_name(path) for path, _ in jax.tree_util.tree_leaves_with_path(tree)]
    assert names.count("cached_key") == names.count("cached_value") == names.count("page_table") == 1
    assert names.count("mamba_state") == names.count("conv_state") == 3 and names.count("ring_key") == 2
    for name in names:
        kv_store.role(name)  # raises on a leaf the store does not know
    assert kv_store.role("mamba_state") == kv_store.Role(kv_store.STATE, 3)
    assert pool.page_leaves == {"cached_key", "cached_value"} and pool.page_readers == 2
    assert pool.per_slot.reason == "state_layers" and pool.prefix is None
    pairs = built[0]["num_key_value_heads"] // 2
    assert tree["full"]["attn"]["cached_key"].shape[2:] == (8, 16), f"{pairs} pairs of 2 x 8 lanes, a page a whole tile of 8"
    assert tree["memory"]["mamba"]["mamba_state"].shape == (3, 16, 128) and tree["memory"]["mamba"]["mamba_state"].dtype == jnp.float32


def test_a_nan_in_the_pages_past_a_rows_cursor_reaches_no_reader(built, served, monkeypatch):
    """The decode step as the chip runs it, all eight readers through the
    kernel in place (steered here: the interpreter, tiny widths). Every
    page of layer n/2 + 1's arena that the row's length does not reach
    (the pages granted and not yet written, page 0, every page of no row)
    holds NaN: the writer's read and the cross layers' are as they were,
    and what the kernel serves is what the ladders do."""
    from tpufw.ops import paged_attend

    pool, seq = served
    ladder = peek(pool)[2]
    real, calls = paged_attend.paged_attention, []

    def interpreted(*a, **k):
        calls.append(a[0].shape)
        return real(*a, interpret=True, block_rows=256, **k)

    monkeypatch.setattr(paged_attend, "serves", lambda *a: True)
    monkeypatch.setattr(paged_attend, "paged_attention", interpreted)
    jax.clear_caches()  # equal models share a trace: this one is steered
    try:
        clean = peek(pool)[2]
        assert len(calls) == pool.page_readers == 2, "the writer and the cross layer, each in place"
        attn = pool.cache["cache"]["full"]["attn"]
        cursor = int(attn["cache_index"][2])
        assert cursor == len(seq) - 1
        held = np.zeros(attn["cached_key"].shape[0], bool)
        held[np.asarray(attn["page_table"][2])[: cursor // PAGE + 1]] = True  # the step's own token lands at ``cursor``
        poison = lambda a: jnp.where(held[:, None, None, None], a, jnp.nan)
        cache = {"cache": {**pool.cache["cache"], "full": {"attn": {
            **attn, "cached_key": poison(attn["cached_key"]), "cached_value": poison(attn["cached_value"])}}}}
        got = peek(pool, cache)[2]
    finally:
        jax.clear_caches()  # and is no later test's
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    assert close(clean, ladder)


def test_a_reused_slot_holds_nothing_of_the_row_before_it(built, served):
    """Slot 2 retired and given another, shorter prompt: its logits are
    the reference's for that prompt alone (state, rings, tails and pages
    of the row before are gone); slot 0, empty all along, agrees too."""
    pool, _ = served
    keys = built[0]
    pool.retire(2)
    for slot, n, seed in ((2, 70, 21), (0, 100, 22)):
        prompt = tokens_of(n, keys, seed).tolist()
        cp = admit_chunked(pool, slot, prompt, 16)
        want = ref_logits(built, prompt + [cp.first_int], [n - 1, n])
        assert int(jnp.argmax(want[0])) == cp.first_int
        assert close(peek(pool)[slot], want[1])


def test_a_reader_one_token_short_and_a_lost_carry_are_told_apart(built, monkeypatch):
    """``scripts/shared_reader_fault.py``'s and ``scripts/
    solar_state_fault.py``'s faults, at these widths in float32: the cross
    layers missing the query's own key, and a scan that starts every
    chunk from zero."""
    import os
    import sys

    keys, ref, params, weights, cls, pc32, _ = built
    tokens = tokens_of(128, keys, 5)
    want = ref_logits(built, tokens, jnp.arange(128))

    @partial(jax.jit, static_argnums=0)
    def cached_call(model, params, cache, toks, at):
        with jax.default_matmul_precision("highest"):
            out, new = model.apply({"params": params, **cache}, toks[None], positions=at + jnp.arange(toks.shape[0])[None],
                                   segment_ids=jnp.ones_like(toks[None]), mutable=["cache"])
        return out[0], {"cache": new["cache"]}

    def in_chunks(model):
        outs, cache = [], {}
        for at in (0, 64):
            out, cache = cached_call(model, params, cache, tokens[at:at + 64], at)
            outs.append(out)
        return jnp.concatenate(outs)

    # (Another model instance a fault: the jitted call is traced anew.)
    assert close(in_chunks(cls(dataclasses.replace(pc32.decode_config(), max_seq_len=253))), want)
    sys.path.insert(0, os.path.join(harness.ROOT, "scripts"))
    try:
        import shared_reader_fault
        import solar_state_fault
    finally:
        sys.path.pop(0)
    monkeypatch.setattr(kv_store, "append", kv_store.append)
    shared_reader_fault.break_program("one_short")
    got = in_chunks(cls(dataclasses.replace(pc32.decode_config(), max_seq_len=254)))
    assert float(jnp.max(jnp.abs(got - want))) > 100 * TOL
    monkeypatch.undo()
    monkeypatch.setattr(phi4flash, "selective_chunk", phi4flash.selective_chunk)
    solar_state_fault.break_program("zero_carry", FAMILY)
    got = in_chunks(cls(dataclasses.replace(pc32.decode_config(), max_seq_len=255)))
    assert close(got[:64], want[:64]), "the first chunk starts from zero anyway"
    assert float(jnp.max(jnp.abs(got[64:] - want[64:]))) > 100 * TOL


def test_the_scheduler_books_what_eight_readers_read_not_what_one_did(built):
    """Through ``serve._SlotScheduler``: ``shared_key_slots_total`` is
    ``attended_key_slots_total`` once for each reader beside the writer
    (one here), by the kind of call; the roles fill ``state_bytes`` and
    ``window_bytes``; prefix reuse is declined by the store's rule."""
    from tpufw.infer import SamplingConfig
    from tpufw.workloads import serve

    keys, ref, params, weights, cls, pc32, _ = built
    model = cls(dataclasses.replace(pc32.decode_config(), max_seq_len=256))
    metrics = serve._Metrics()
    sched = serve._SlotScheduler(
        model, params, eos_id=None, default_sampling=SamplingConfig(temperature=0.0),
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=True,
        prefill_chunk_pages=2, metrics=metrics,
    )
    prompt = tokens_of(72, keys, 31).tolist()
    with jax.default_matmul_precision("highest"):
        out = sched.submit([prompt], 5)[0][0]
    reg = metrics.registry
    shared = reg.counter("tpufw_serve_shared_key_slots_total")
    attended = reg.counter("tpufw_serve_attended_key_slots_total").value()
    assert shared.value(call="decode") > 0 and shared.value(call="chunk") > 0
    assert shared.value(call="decode") + shared.value(call="chunk") == (sched._pool.page_readers - 1) * attended
    assert reg.counter("tpufw_serve_prefix_declined_total").value(reason="state_layers") == 1
    pool = sched._pool
    inner, n = 2 * keys["hidden_size"], 16
    assert reg.gauge("tpufw_serve_state_bytes").value() == pool.state_bytes == sched.n_slots * 3 * (inner * n * 4 + 3 * inner * 4)
    assert reg.gauge("tpufw_serve_window_bytes").value() == pool.window_bytes > 0
    assert reg.counter("tpufw_serve_window_key_slots_total").value() > 0
    assert reg.counter("tpufw_serve_state_live_bytes_total").value() > 0
    seq = prompt + out
    want = ref_logits(built, seq[:-1], jnp.arange(len(prompt) - 1, len(seq) - 1))
    served = want[jnp.arange(5), jnp.asarray(out)]
    assert float(jnp.max(jnp.max(want, axis=-1) - served)) < 1e-3


def test_no_file_of_the_pools_or_the_server_names_the_family():
    import pathlib

    import tpufw

    root = pathlib.Path(tpufw.__file__).parent
    for source in [*(root / "infer").glob("*.py"), root / "workloads" / "serve.py"]:
        text = source.read_text().lower()
        for spelled in ("phi4", "phi-4", "mamba", "gmu", "sambay"):
            assert spelled not in text, (source.name, spelled)
