"""Paged, prefix-shared, int8 KV cache (tpufw.infer.pages / .prefix).

Contracts, all on CPU with the tiny model:

- PARITY: rows decoded through the PAGED pool (page arena + per-slot
  page table, gather/scatter reads) emit exactly the one-shot
  ``generate`` path's greedy tokens at matching precision — the
  physical layout must be invisible to the math (the gather
  reconstructs logical rows in slot order, so even the summation
  order matches).
- SHAPE STABILITY: occupancy, page-table contents, and cursors are
  DATA. After the first chunk ladder is traced, page churn (release +
  re-admit at a NEW prompt length) adds ZERO decode or insert traces.
- PREFIX SHARING: a second request whose prompt shares full pages
  attaches them by reference (refcount 2, same physical ids) and
  still emits the cold path's exact tokens; divergence after the
  shared point is structural copy-on-write (private pages), never a
  device copy.
- INT8: per-token symmetric quantization bounds the roundtrip error,
  and the int8 pool decodes the tiny model to the fp greedy tokens.
- PRESSURE: the allocator is all-or-nothing with refcount/hold
  lifetime rules; the trie evicts refcount-0 leaves LRU-first; the
  scheduler defers admissions that don't fit the arena and rejects
  rows that never could.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.infer import SamplingConfig, generate_text
from tpufw.infer import pages as pages_mod
from tpufw.infer import slots as slots_mod
from tpufw.infer.prefix import PrefixCache
from tpufw.models import LLAMA_CONFIGS, Llama

GREEDY = SamplingConfig(temperature=0.0)
MAX_NEW = 6
PAGE = 16
N_SLOTS = 4


@pytest.fixture(scope="module")
def tiny_paged():
    base = LLAMA_CONFIGS["llama3_tiny"].decode_config()
    cfg = dataclasses.replace(base, max_seq_len=64)
    row_model = Llama(cfg)
    params = jax.jit(row_model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, row_model, params


def _paged_pool(cfg, row_model, params, kv_quant="", n_pages=None):
    pcfg = dataclasses.replace(
        cfg,
        kv_page=PAGE,
        kv_pages=(
            n_pages
            if n_pages is not None
            else N_SLOTS * (cfg.max_seq_len // PAGE) + 1
        ),
        kv_quant=kv_quant,
    )
    return pages_mod.PagedSlotPool.create_paged(
        Llama(pcfg),
        row_model,
        params,
        N_SLOTS,
        sampling=GREEDY,
        eos_id=None,
    )


def _admit(pool, slot, prompt, i, max_new=MAX_NEW):
    """The scheduler's paged admission flow: acquire -> (shared or
    cold) prefill -> scatter-insert -> register in the trie."""
    rng = jax.random.fold_in(jax.random.key(0), i)
    grant = pool.acquire_pages(prompt, len(prompt) + max_new - 1)
    assert grant is not None
    ids, shared_n = grant
    if shared_n:
        cache, _f, first_int, _d, seen = pool.prefill_shared(
            prompt, ids[:shared_n], rng
        )
    else:
        cache, _f, first_int, _d, seen = slots_mod.prefill_row(
            pool.row_model,
            pool.params,
            prompt,
            rng,
            sampling=GREEDY,
            eos_id=None,
            pad_to=len(prompt),
        )
    pool.insert_paged(
        slot, cache, first_int, len(prompt), max_new - 1,
        ids, shared_n, row_seen=seen,
    )
    pool.register_prefix(prompt, ids)
    return first_int, shared_n


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


def test_paged_decode_bit_equal_contiguous(tiny_paged):
    cfg, row_model, params = tiny_paged
    prompts = [[1, 5, 9], [2, 7], list(range(3, 37))]
    want = generate_text(
        row_model, params, prompts, max_new_tokens=MAX_NEW,
        sampling=GREEDY,
    )
    pool = _paged_pool(cfg, row_model, params)
    firsts = {}
    for i, p in enumerate(prompts):
        firsts[i], _ = _admit(pool, i, p, i)
    rows = _decode_all(pool, firsts)
    assert [rows[i] for i in range(len(prompts))] == want
    # Contiguous insert is a guard-railed dead end on the paged pool.
    with pytest.raises(TypeError):
        pool.insert(0, None, 0, 1, 1)


def test_zero_retrace_across_page_churn(tiny_paged):
    cfg, row_model, params = tiny_paged
    pool = _paged_pool(cfg, row_model, params)
    firsts = {}
    for i, p in enumerate([[1, 5, 9], [2, 7]]):
        firsts[i], _ = _admit(pool, i, p, i)
    _decode_all(pool, firsts)
    t0 = dict(slots_mod.TRACE_COUNTS), dict(pages_mod.TRACE_COUNTS)
    # Churn: free a slot, admit a NEW prompt length into it, decode.
    freed = pool.release_slot(1)
    assert freed > 0
    fi, _ = _admit(pool, 1, [4, 4, 4, 4], 9)
    _decode_all(pool, {1: fi})
    t1 = dict(slots_mod.TRACE_COUNTS), dict(pages_mod.TRACE_COUNTS)
    assert t1[0]["decode_steps"] == t0[0]["decode_steps"], (t0, t1)
    assert t1[1]["paged_insert"] == t0[1]["paged_insert"], (t0, t1)


def test_prefix_share_matches_cold_and_cow(tiny_paged):
    cfg, row_model, params = tiny_paged
    shared = list(range(40, 76))  # 36 tokens = 2 full pages + 4
    pa = shared + [7, 9]
    pb = shared + [11, 3, 5]
    want = generate_text(
        row_model, params, [pa, pb], max_new_tokens=MAX_NEW,
        sampling=GREEDY,
    )
    pool = _paged_pool(cfg, row_model, params)
    fa, sn_a = _admit(pool, 0, pa, 0)
    fb, sn_b = _admit(pool, 1, pb, 1)
    assert sn_a == 0 and sn_b == 2  # second admission attached 2 pages
    # Shared pages are the SAME physical ids, refcounted per row.
    assert pool.slot_pages[1][:2] == pool.slot_pages[0][:2]
    assert all(
        pool.allocator.refs[pid] == 2 for pid in pool.slot_pages[0][:2]
    )
    # Copy-on-write: past the shared point the rows' pages are private.
    assert set(pool.slot_pages[0][2:]).isdisjoint(pool.slot_pages[1][2:])
    rows = _decode_all(pool, {0: fa, 1: fb})
    assert rows[0] == want[0]  # donor row unperturbed by the share
    assert rows[1] == want[1]  # shared tokens == cold prefill tokens
    # Retiring the donor must NOT free the trie-held shared pages.
    held = list(pool.slot_pages[0][:2])
    pool.release_slot(0)
    assert all(pid in pool.allocator.refs or pid in pool.allocator.held
               for pid in held)
    rows_b = _decode_all(pool, {1: [rows[1][-1]]}, max_new=2)
    assert isinstance(rows_b[1][-1], int)


@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_two_whole_prompt_prefix_hits_in_a_row(kind, tiny_paged):
    """``prefill_shared`` twice running: the attach and the suffix
    prefill donate the row canvas, so each hit needs live buffers of
    its own from the shapes the pool found once (K/V heads and MLA
    latents; a model with per-slot state gets no trie)."""
    if kind == "gqa":
        cfg, row_model, params = tiny_paged
        pool = _paged_pool(cfg, row_model, params)
    else:
        from tpufw.models.deepseek import DEEPSEEK_CONFIGS, Deepseek

        cfg = dataclasses.replace(
            DEEPSEEK_CONFIGS["deepseek_tiny"].decode_config(),
            max_seq_len=64,
        )
        row_model = Deepseek(cfg)
        params = jax.jit(row_model.init)(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        pcfg = dataclasses.replace(
            cfg, kv_page=PAGE, kv_pages=N_SLOTS * (64 // PAGE) + 1
        )
        pool = pages_mod.PagedSlotPool.create_paged(
            Deepseek(pcfg), row_model, params, N_SLOTS,
            sampling=GREEDY, eos_id=None,
        )
    shared = list(range(40, 76))  # 36 tokens = 2 full pages + 4
    prompts = [shared + [7, 9], shared + [11, 3, 5], shared + [2]]
    want = generate_text(
        row_model, params, prompts, max_new_tokens=MAX_NEW,
        sampling=GREEDY,
    )
    firsts, hits = {}, []
    for i, p in enumerate(prompts):
        firsts[i], shared_n = _admit(pool, i, p, i)
        hits.append(shared_n)
    assert hits == [0, 2, 2]
    assert pool.row_shape_traces == 1
    rows = _decode_all(pool, firsts)
    assert [rows[i] for i in range(3)] == want


def test_int8_kv_quant_roundtrip_tolerance():
    from tpufw.ops.quant import dequantize_kv, quantize_kv

    x = jax.random.normal(jax.random.key(3), (3, 5, 4, 8), jnp.float32)
    q, scale = quantize_kv(x, n_feat=2)
    assert q.dtype == jnp.int8 and scale.shape == (3, 5)
    back = np.asarray(dequantize_kv(q, scale, jnp.float32))
    amax = np.max(np.abs(np.asarray(x)), axis=(2, 3), keepdims=True)
    # Symmetric per-token int8: error bounded by half a quant step.
    assert np.all(np.abs(back - np.asarray(x)) <= amax / 127.0)


def test_int8_pool_decodes_to_fp_greedy(tiny_paged):
    cfg, row_model, params = tiny_paged
    prompts = [[1, 5, 9], list(range(3, 37))]
    want = generate_text(
        row_model, params, prompts, max_new_tokens=MAX_NEW,
        sampling=GREEDY,
    )
    pool = _paged_pool(cfg, row_model, params, kv_quant="int8")
    # The arena really is int8 with per-page fp32 scales.
    flat = jax.tree_util.tree_flatten_with_path(pool.cache)[0]
    names = [str(p[-1]) for p, _ in flat]
    arenas = [
        leaf for p, leaf in flat if "cached_key" in str(p[-1])
        and "scale" not in str(p[-1])
    ]
    assert arenas and all(a.dtype == jnp.int8 for a in arenas)
    assert any("scale" in n for n in names)
    firsts = {}
    for i, p in enumerate(prompts):
        firsts[i], _ = _admit(pool, i, p, i)
    rows = _decode_all(pool, firsts)
    # Tiny-model logits have wide argmax margins; int8 KV (max relative
    # error 1/254 per token) must not flip the greedy path here.
    assert [rows[i] for i in range(len(prompts))] == want


def test_page_allocator_refcount_hold_lifetime():
    a = pages_mod.PageAllocator(5)  # page 0 reserved -> 4 usable
    assert a.capacity == 4 and a.n_free == 4
    ids = a.alloc(3)
    assert ids is not None and len(ids) == 3 and 0 not in ids
    assert a.alloc(2) is None  # all-or-nothing: only 1 free
    assert a.in_use == 3
    a.ref(ids[:1])  # second row references the first page
    assert a.release(ids[:1]) == 0  # refcount 2 -> 1: stays resident
    assert a.release(ids) == 3  # last refs drop: all freed
    assert a.n_free == 4 and a.freed_total == 3
    ids = a.alloc(2)
    a.hold(ids[:1])  # trie adoption
    assert a.release(ids) == 1  # held page survives its row
    assert a.in_use == 1
    assert a.drop(ids[:1]) == 1  # trie eviction frees it
    assert a.in_use == 0
    with pytest.raises(ValueError):
        pages_mod.PageAllocator(1)  # junk sink alone is not an arena


def test_prefix_trie_eviction_under_pressure():
    a = pages_mod.PageAllocator(5)  # 4 usable
    trie = PrefixCache(2)
    ids1 = a.alloc(2)
    a.hold(trie.insert([1, 2, 3, 4], ids1))
    assert a.release(ids1) == 0  # both pages trie-held
    ids2 = a.alloc(2)
    # Shares chunk (1,2) -> keeps the EXISTING page; adopts only (9,9).
    adopted = trie.insert([1, 2, 9, 9], ids2)
    assert adopted == [ids2[1]]
    a.hold(adopted)
    assert a.release(ids2) == 1  # duplicate (1,2) copy dies with row
    assert len(trie) == 3 and a.in_use == 3 and a.n_free == 1
    # Pressure: evicting 2 refcount-0 leaves frees real pages.
    dropped = trie.evict(2, a)
    assert len(dropped) == 2 and a.n_free == 3 and len(trie) == 1


def test_scheduler_page_budget_admission(tiny_paged):
    from tpufw.workloads.serve import _Metrics, _SlotScheduler

    _cfg, _row_model, params = tiny_paged
    model = Llama(LLAMA_CONFIGS["llama3_tiny"].decode_config())
    metrics = _Metrics()
    # 6-usable-page arena; three rows of 3 pages each cannot be
    # co-resident — the third defers until a retire frees pages.
    sched = _SlotScheduler(
        model, params,
        eos_id=None, default_sampling=GREEDY, seed_base=0,
        metrics=metrics, page=16, arena_pages=7,
    )
    prompts = [list(range(10 + i, 40 + i)) for i in range(3)]
    want = generate_text(
        model, params, prompts, max_new_tokens=MAX_NEW, sampling=GREEDY
    )
    outs, _bw = sched.submit(prompts, MAX_NEW, None)
    assert outs == want
    freed = metrics.registry.counter(
        "tpufw_serve_pages_freed_total"
    ).value()
    assert freed > 0
    assert sched.pages_in_use < sched.pages_total == 6
    # A row that can NEVER fit the arena is rejected at submit.
    with pytest.raises(ValueError):
        sched.submit([list(range(100))], 29, None)


def test_deepseek_paged_parity():
    from tpufw.models.deepseek import DEEPSEEK_CONFIGS, Deepseek

    base = DEEPSEEK_CONFIGS["deepseek_tiny"].decode_config()
    cfg = dataclasses.replace(base, max_seq_len=64)
    row_model = Deepseek(cfg)
    params = jax.jit(row_model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompts = [[1, 5, 9], [2, 7]]
    max_new = 4
    want = generate_text(
        row_model, params, prompts, max_new_tokens=max_new,
        sampling=GREEDY,
    )
    pcfg = dataclasses.replace(
        cfg, kv_page=PAGE, kv_pages=2 * (64 // PAGE) + 1
    )
    pool = pages_mod.PagedSlotPool.create_paged(
        Deepseek(pcfg), row_model, params, 2,
        sampling=GREEDY, eos_id=None,
    )
    firsts = {}
    for i, p in enumerate(prompts):
        firsts[i], _ = _admit(pool, i, p, i, max_new=max_new)
    rows = _decode_all(pool, firsts, max_new=max_new)
    assert [rows[i] for i in range(len(prompts))] == want


def _seam_family(name):
    if name == "llama":
        return Llama, LLAMA_CONFIGS["llama3_tiny"].decode_config()
    if name == "deepseek":
        from tpufw.models.deepseek import DEEPSEEK_CONFIGS, Deepseek

        return Deepseek, DEEPSEEK_CONFIGS["deepseek_tiny"].decode_config()
    from benchmarks import harness

    keys = harness.model_keys(
        harness.load_json(f"benchmarks/configs/rehearse/{name}.json")
    )
    cls, cfg = harness.family_modules(name)[1].program_model(
        keys, {"moe_dispatch": "sorted"}
    )
    if name in ("falcon_h1", "olmo_hybrid"):
        # Its layers (olmo_hybrid: its PERIODS of four layers) are
        # alike, so its trunk scans: STATE and PAGE leaves of ONE unit,
        # both stacked [L, ...] in front.
        cfg = dataclasses.replace(cfg, scan_layers=True)
    return cls, cfg.decode_config()


@pytest.mark.parametrize(
    "family",
    ["llama", "deepseek", "solar_open2", "laguna", "falcon_h1", "olmo_hybrid"],
)
def test_every_cache_leaf_has_a_role_in_the_store(family):
    """The seam tpufw.ops.kv_store owns: whatever a pool or a row twin
    holds resolves through ``role()`` (the programs here switch on
    nothing else: no file of tpufw/infer spells a leaf's name), and the
    models spell no layout of their own."""
    import pathlib

    from tpufw.ops import kv_store

    cls, cfg = _seam_family(family)
    cfg = dataclasses.replace(cfg, max_seq_len=64)
    row_model = cls(cfg)
    params = jax.eval_shape(
        row_model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    kinds = set()
    for quant in ("", "int8"):
        paged = cls(dataclasses.replace(
            cfg, kv_page=PAGE, kv_pages=2 * (64 // PAGE) + 1, kv_quant=quant
        ))
        trees = (
            pages_mod.paged_pool_cache(paged, params, 2),
            pages_mod._row_cache_shapes(row_model, params),
        )
        for tree in trees:
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
                r = kv_store.path_role(path)
                kinds.add(r.kind)
                if r.kind in (kv_store.PAGE, kv_store.STATE):
                    # unstacked rank: nn.scan may stack layers in front
                    assert leaf.ndim >= r.rank, (path, leaf.shape, r)
    assert {
        kv_store.PAGE, kv_store.SCALE, kv_store.SEGMENT, kv_store.TABLE,
        kv_store.CURSOR,
    } <= kinds
    assert (kv_store.STATE in kinds) == (
        family in ("solar_open2", "falcon_h1", "olmo_hybrid")
    )
    assert (kv_store.RING in kinds) == (family == "laguna")
    if family == "falcon_h1":
        # One scanned block holds a page pair AND per-slot state.
        block = pages_mod.paged_pool_cache(paged, params, 2)["cache"]["layers"]
        n_layers = cfg.n_layers
        assert block["attn"]["cached_key"].shape[0] == n_layers
        assert block["ssm"]["ssm_state"].shape[:2] == (n_layers, 2)
        assert block["ssm"]["conv_state"].shape[:2] == (n_layers, 2)
    if family == "olmo_hybrid":
        # One scanned PERIOD: per-slot state in three of its blocks, a
        # page pair in the fourth, each stacked by period.
        period = pages_mod.paged_pool_cache(paged, params, 2)["cache"]["layers"]
        n_periods = cfg.n_layers // len(cfg.period)
        for j in range(3):
            gdn = period[f"linear_{j}"]["gdn"]
            assert gdn["gdn_state"].shape[:2] == (n_periods, 2)
            assert gdn["conv_state"].shape[:2] == (n_periods, 2)
        assert period["full_3"]["attn"]["cached_key"].shape[0] == n_periods
    models = pathlib.Path(pages_mod.__file__).parents[1] / "models"
    for source in (
        "llama.py", "deepseek.py", "laguna.py", "falcon_h1.py",
        "olmo_hybrid.py",
    ):
        text = (models / source).read_text()
        for spelled in (
            '"page_table"', '"cache_index"', '"cached_segment_ids"',
            '"_scale"', "self.variable(", '"ring_slot"', '"ring_segment"',
        ):
            assert spelled not in text, (source, spelled)
    for source in pathlib.Path(pages_mod.__file__).parent.glob("*.py"):
        text = source.read_text()
        # ("cache_index" is also a key of the slot bundle's wire format.)
        for name in set(kv_store._LEAVES) - {"cache_index"}:
            assert f'"{name}"' not in text, (source.name, name)


# ---- the live prefix of a row (tpufw.ops.kv_store's ladder of key lengths)

LONG_S, LONG_NEW = 1024, 24  # two rungs under a floor of 512: 512, 1024


def _long_family(family, monkeypatch):
    from tpufw.ops import kv_store

    # The ladder's floor is 2,048 slots. These tests put it at 512, so
    # that a 1,024-slot row has two rungs at a test's cost; the rule at
    # its own floor is tests/test_kv_store.py's. No other test builds a
    # program at LONG_S, so no trace made under another floor is reused.
    monkeypatch.setattr(kv_store, "MIN_RUNG", 512)
    cls, cfg = _seam_family(family)
    cfg = dataclasses.replace(cfg, max_seq_len=LONG_S)
    assert kv_store.key_ladder(LONG_S, PAGE) == (512, LONG_S)
    row_model = cls(cfg)
    params = jax.jit(row_model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    pcfg = dataclasses.replace(
        cfg, kv_page=PAGE, kv_pages=2 * (LONG_S // PAGE) + 1
    )
    pool = pages_mod.PagedSlotPool.create_paged(
        cls(pcfg), row_model, params, 2, sampling=GREEDY, eos_id=None
    )
    return row_model, params, pool


@pytest.mark.parametrize("family", ["llama", "deepseek"])
def test_paged_decode_matches_contiguous_across_two_rungs(family, monkeypatch):
    """A 500-token row decodes past slot 512 beside a 3-token one: the
    paged pool's steps read 512 keys, then 1,024 (the longest LIVE row
    chooses), the one-shot contiguous path likewise under its scalar
    cursor, and the served tokens are equal."""
    row_model, params, pool = _long_family(family, monkeypatch)
    long_prompt = np.random.default_rng(5).integers(1, 200, 500).tolist()
    prompts = [long_prompt, [1, 5, 9]]
    want = generate_text(
        row_model, params, prompts, max_new_tokens=LONG_NEW, sampling=GREEDY
    )
    firsts = {}
    for i, p in enumerate(prompts):
        firsts[i], _ = _admit(pool, i, p, i, max_new=LONG_NEW)
    # A pool of two has one row rung: 2 rows x the key rung, of 2 x LONG_S.
    reads = {
        pool.attended_keys([[len(long_prompt) + n]])
        for n in range(1, LONG_NEW)
    }
    assert reads == {(2 * 512, 2 * LONG_S), (2 * LONG_S, 2 * LONG_S)}
    rows = _decode_all(pool, firsts, max_new=LONG_NEW, chunk=4)
    assert [rows[0], rows[1]] == want


def test_tpu_lowering_gathers_the_whole_row_only_in_the_top_rung(monkeypatch):
    """Lowered for the chip, a multi-rung decode step holds the gather
    of every row's whole table ([B, S/page] pages) only inside the top
    branch of the store's switch: the lower branch gathers half of it
    and nothing outside the switch gathers pages at all. (A pool of two
    has no row rung under its width; tests/test_kv_store.py holds the
    row ladder's branches.) The latent cache's pool: on the chip a K/V
    pool's decode step has no switch, it reads the arena in place
    (tests/test_program_text.py holds that program)."""
    import re

    _, _, pool = _long_family("deepseek", monkeypatch)
    text = (
        slots_mod._decode_steps_jit.trace(
            pool.model, pool.params, pool.cache, pool.token, pool.pos,
            pool.done, pool.remaining, pool.seen,
            jax.random.split(jax.random.key(1), 2),
            sampling=GREEDY, pad_id=0, eos_id=None,
        )
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )
    from tests.test_kv_store import case_branches

    case = re.search(
        r'"stablehlo\.case"\(.*?^\s*\}\) : \(tensor<i32>\)', text, re.M | re.S
    )
    assert case and text.count('"stablehlo.case"') == 1
    # Each branch with the text of the function it calls (the store's
    # ``_read_rows``, lowered once a branch).
    branches = case_branches(text)
    assert len(branches) == 2  # the ladder's rungs, 512 and 1,024
    per_row = LONG_S // PAGE

    def page_gathers(part, n_pages):
        return re.findall(
            rf'"stablehlo\.gather".*-> tensor<2x{n_pages}x{PAGE}[x>]', part
        )

    main = text[text.index("func.func public @main"):]
    outside = main[:main.index("\n  }\n")].replace(case.group(0), "")
    for n in (per_row // 2, per_row):
        assert not page_gathers(outside, n)
    # Both latent leaves' pages and their segment ids, at each rung's own
    # length.
    assert len(page_gathers(branches[0], per_row // 2)) == 3
    assert not page_gathers(branches[0], per_row)
    assert len(page_gathers(branches[1], per_row)) == 3
    assert not page_gathers(branches[1], per_row // 2)


# ---- the live rows of a pool (tpufw.ops.kv_store's ladder of row counts)

@pytest.mark.parametrize("live", [1, 3, 8])
@pytest.mark.parametrize("family", ["llama", "deepseek"])
def test_a_pool_reads_its_live_rows_and_the_scheduler_counts_them(
    family, live, monkeypatch
):
    """A paged pool of 8 slots (row rungs 1 and 8) with 1, 3 and 8 rows
    live serves the tokens the one-shot path decodes, which reads every
    row under its scalar cursor: a GQA store and DeepSeek's latent one.
    The scheduler's ``attended_key_slots_total`` is K x L summed over
    the steps it dispatched, the pair by the store's rule from the
    DEVICE's ``done`` and ``remaining`` as each chunk started, and
    ``row_key_slots_total`` every slot's whole row. (Rows of 96 slots
    under a floor of 48 have two key rungs, and so a row rung: a row of
    one key rung keeps no switch. No other test builds a program over a
    96-slot row, so no trace made under another floor is reused.)"""
    from tpufw.infer.speculative import _pool_cursor
    from tpufw.ops import kv_store
    from tpufw.workloads.serve import _Metrics, _SlotScheduler

    monkeypatch.setattr(kv_store, "MIN_RUNG", 48)
    cls, cfg = _seam_family(family)
    cfg = dataclasses.replace(cfg, max_seq_len=96)
    row_model = cls(cfg)
    params = jax.jit(row_model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.default_rng(live)
    prompts = [
        rng.integers(1, 200, int(n)).tolist()
        for n in rng.integers(3, 30, live)
    ]
    want = generate_text(
        row_model, params, prompts, max_new_tokens=11, sampling=GREEDY
    )
    metrics = _Metrics()
    sched = _SlotScheduler(
        row_model, params, eos_id=None, default_sampling=GREEDY,
        seed_base=0, page=PAGE, arena_pages=None, prefix_cache=False,
        prefill_chunk_pages=0, metrics=metrics,
    )
    n = sched.n_slots
    assert kv_store.pool_ladders(96, PAGE, n) == ((1, 8), (48, 96))
    assert kv_store.pool_ladders(64, PAGE, n) == ((8,), (64,))
    assert sched.submit(prompts, 11)[0] == want

    # What the device held as each decode chunk started. (The pool is
    # built by the first admission, so its method is wrapped after one
    # request and the counters are read from there.)
    pool, seen = sched._pool, []
    steps = pool.decode_steps

    def recorded(keys):
        seen.append((
            len(keys), np.asarray(pool.done), np.asarray(pool.remaining),
            np.asarray(_pool_cursor(pool.cache, n)),
        ))
        return steps(keys)

    pool.decode_steps = recorded
    read = metrics.registry.counter("tpufw_serve_attended_key_slots_total")
    whole = metrics.registry.counter("tpufw_serve_row_key_slots_total")
    read0, whole0 = read.value(), whole.value()
    assert sched.submit(prompts, 11)[0] == want
    expect = calls = 0
    for k, done, remaining, cursor in seen:
        for i in range(k):
            alive = ~done & (remaining > i)
            slots = int((cursor[alive] + i + 1).max()) if alive.any() else 0
            k, length = kv_store.attended_pair(
                pool.model.cfg, n, int(alive.sum()), slots
            )
            expect += k * length
            calls += 1
    assert calls >= 10 and read.value() - read0 == expect
    assert whole.value() - whole0 == calls * n * 96
    # One chunk of 16 steps: ten with the request's rows live (one row
    # whole, or all 8 at the lower key rung: no row passes 48 slots),
    # and six with none, one row whole.
    assert expect == 10 * {1: 1 * 96, 3: 8 * 48, 8: 8 * 48}[live] + 6 * 96
