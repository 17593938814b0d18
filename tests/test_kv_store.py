"""The KV store alone (tpufw.ops.kv_store), without a model around it.

One toy flax module appends random tokens through ``append`` and the
contracts of every layout are held against the tokens themselves and
against the scalar-cursor contiguous store, the reference:

- every store shows, at each written slot, what the scalar-cursor store
  shows (int8 within one step of ``quantize_kv``), rows at staggered
  cursors included, for t = 1 (decode) and t = 4 (a verify block);
- ``kv_segment_ids`` is 0 outside what a row has written;
- a row stepped at ``max_seq_len`` writes into its own last slots / its
  own last page, and into reserved page 0 once its table row is zeroed:
  never into a neighbour's.
"""

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from tpufw.ops import kv_store

B, S, PAGE, PREFILL, STEPS = 3, 32, 8, 5, 3
PER_ROW = S // PAGE
LAYOUTS = {
    "kv": {"cached_key": (2, 4), "cached_value": (2, 4)},
    "latent": {"cached_ckv": (6,), "cached_kpe": (4,)},
}


@dataclasses.dataclass(frozen=True)
class Cfg:
    max_seq_len: int = S
    dtype: Any = jnp.bfloat16
    kv_page: int = 0
    kv_pages: int = 0
    kv_quant: str = ""


class Store(nn.Module):
    cfg: Cfg

    @nn.compact
    def __call__(self, new, segment_ids):
        read, seg, q_slots = kv_store.append(
            self, self.cfg, new, segment_ids
        )
        # One rung of each ladder at this size: ``read`` hands the whole
        # rows through.
        views, kv_seg = read(lambda views, kv_seg, _: (views, kv_seg))
        return views, seg, kv_seg, q_slots


def tokens(layout, t, seed, b=B):
    """[b, t, *feat] per leaf, already bf16 so a bf16 store is exact."""
    return {
        name: jax.random.normal(
            jax.random.fold_in(jax.random.key(seed), i), (b, t, *feat)
        ).astype(jnp.bfloat16)
        for i, (name, feat) in enumerate(LAYOUTS[layout].items())
    }


def fresh(cfg, layout, cursors=None):
    """A zeroed cache; ``cursors`` [B] turns the contiguous store's
    scalar cursor into per-row ones, as tpufw.infer.slots.pool_cache
    does, and is where a paged row starts. Every paged row owns
    ``PER_ROW`` private pages, in row order after reserved page 0."""
    cache = jax.tree_util.tree_map(  # init has appended once: zero it
        jnp.zeros_like,
        Store(cfg).init(jax.random.key(0), tokens(layout, 1, 0), None)["cache"],
    )
    if cursors is not None:
        cache["cache_index"] = jnp.asarray(cursors, jnp.int32)
    if cfg.kv_page:
        cache["page_table"] = 1 + jnp.arange(B * PER_ROW, dtype=jnp.int32
                                             ).reshape(B, PER_ROW)
    return cache


def run(cfg, cache, blocks):
    """Append ``blocks`` in turn; the last call's return and the cache."""
    out = None
    for new in blocks:
        out, mutated = Store(cfg).apply(
            {"cache": cache}, new, None, mutable=["cache"]
        )
        cache = mutated["cache"]
    return out, cache


def config(store):
    if store.startswith("paged"):
        return Cfg(
            kv_page=PAGE, kv_pages=B * PER_ROW + 1,
            kv_quant="int8" if store == "paged_int8" else "",
        )
    return Cfg()


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize(
    "store", ["scalar", "row_cursor", "paged_bf16", "paged_int8"]
)
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_every_store_shows_what_the_scalar_cursor_store_shows(
    layout, store, t
):
    blocks = [tokens(layout, PREFILL, 1)] + [
        tokens(layout, t, 2 + i) for i in range(STEPS)
    ]
    n = PREFILL + STEPS * t
    (ref_views, _, ref_seg, ref_slots), _ = run(
        Cfg(), fresh(Cfg(), layout), blocks
    )
    # The reference itself: the tokens, in order, from slot 0.
    for name in ref_views:
        want = jnp.concatenate([blk[name] for blk in blocks], axis=1)
        np.testing.assert_array_equal(ref_views[name][:, :n], want)
    assert ref_slots.shape == (1, t) and int(ref_slots[0, -1]) == n - 1

    cfg = config(store)
    starts = [0, 0, 0] if store == "scalar" else [0, 3, 6]
    cache = fresh(cfg, layout, None if store == "scalar" else starts)
    (views, seg, kv_seg, q_slots), cache = run(cfg, cache, blocks)
    np.testing.assert_array_equal(seg, jnp.ones((B, t), jnp.int32))
    for row, at in enumerate(starts):
        for name, view in views.items():
            assert view.shape == (B, S, *LAYOUTS[layout][name])
            assert view.dtype == cfg.dtype
            got = view[row, at:at + n].astype(jnp.float32)
            want = ref_views[name][row, :n].astype(jnp.float32)
            if cfg.kv_quant:
                feat = tuple(range(1, want.ndim))
                step = jnp.max(jnp.abs(want), axis=feat, keepdims=True) / 127
                assert bool(jnp.all(jnp.abs(got - want) <= step))
            else:
                np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(kv_seg[row, at:at + n], ref_seg[row, :n])
        assert not kv_seg[row, :at].any() and not kv_seg[row, at + n:].any()
        assert int(q_slots[row if store != "scalar" else 0, -1]) == at + n - 1
    if store == "scalar":
        return

    # Row 1 has reached max_seq_len and is stepped all the same (a done
    # row under static shapes): the clamped window is its own tail, then
    # (retired: table row zeroed) the tail of reserved page 0.
    at_end = dict(cache, cache_index=cache["cache_index"].at[1].set(S))
    cases = [(at_end, 1 + 1 * PER_ROW + PER_ROW - 1)]
    if cfg.kv_page:
        cases.append(
            (dict(at_end, page_table=at_end["page_table"].at[1].set(0)), 0)
        )
    for before, own_page in cases:
        _, after = run(cfg, before, [tokens(layout, t, 9)])
        cursors = np.asarray(before["cache_index"])
        may = np.zeros((cfg.kv_pages, PAGE) if cfg.kv_page else (B, S), bool)
        for row, cur in enumerate(cursors):
            for slot in range(min(cur, S - t), min(cur, S - t) + t):
                if cfg.kv_page:
                    page = int(before["page_table"][row, slot // PAGE])
                    may[page, slot % PAGE] = True
                else:
                    may[row, slot] = True
        own = (own_page, slice(PAGE - t, PAGE)) if cfg.kv_page else (
            1, slice(S - t, S)
        )
        assert may[own].all()
        for name, was in before.items():
            was, now = np.asarray(was), np.asarray(after[name])
            kind = kv_store.role(name).kind
            if kind == kv_store.TABLE:
                np.testing.assert_array_equal(now, was)
            elif kind == kv_store.CURSOR:
                np.testing.assert_array_equal(now, was + t)
            else:
                np.testing.assert_array_equal(now[~may], was[~may])
                assert kind != kv_store.PAGE or (now[own] != was[own]).any()


def test_an_unknown_leaf_has_no_role():
    assert kv_store.role("cached_key_scale") == kv_store.Role(
        kv_store.SCALE, 2, "cached_key"
    )
    for name in ("cached_index_keys", "page_table_scale", "_scale", ""):
        with pytest.raises(ValueError, match="unknown cache leaf"):
            kv_store.role(name)
    with pytest.raises(ValueError, match="rank 3"):
        Store(Cfg()).init(
            jax.random.key(0), {"cached_key": jnp.zeros((B, 1, 4))}, None
        )


# ---- the live prefix: a ladder of key lengths chosen in the program ----

S2, T2 = 8192, 3  # three rungs: 2048, 4096, 8192
PER_ROW2 = S2 // PAGE
RUNG = 4096
STORES = ["scalar", "row_cursor", "paged", "paged_int8"]


B8 = 16  # a pool with a row ladder: 2, 16
#: Which rows of a pool of ``B8`` are live, by where the dead ones sit,
#: and the row rung each takes.
LIVE_ROWS = {
    "start": ([0] * 15 + [1], 2),  # one dead row inside K
    "middle": ([1] + [0] * 14 + [1], 2),
    "interleaved": ([0, 1, 0, 1, 0, 1] + [0] * 10, B8),
    "all": ([1] * B8, B8),
    "none": ([0] * B8, 2),
}


def config2(store, b=B):
    if store.startswith("paged"):
        return Cfg(
            max_seq_len=S2, dtype=jnp.float32, kv_page=PAGE,
            kv_pages=b * PER_ROW2 + 1,
            kv_quant="int8" if store == "paged_int8" else "",
        )
    return Cfg(max_seq_len=S2, dtype=jnp.float32)


def queries(layout, b, t):
    q = jax.random.normal(jax.random.key(3), (b, t, 2, 10), jnp.float32)
    return q[..., :4] if layout == "kv" else q


def rows_read(alive):
    """The rows a read under ``alive`` gathers: live first, stably, up to
    the row rung. In numpy, beside the program's ``argsort``."""
    alive = np.asarray(alive, bool)
    k, _ = kv_store.attended_pair(
        config2("paged", len(alive)), len(alive), int(alive.sum()), 1
    )
    return np.argsort(~alive, kind="stable")[:k]


def attention_over(layout):
    """The family's contraction as a function of the L-long views and the
    rows' own queries: GQA's through ``xla_attention``, the latent one
    MLA's absorbed scores."""
    from tpufw.ops.attention import attention_mask, xla_attention

    def attend(views, kv_seg, q, seg, q_slots):
        if layout == "kv":
            return xla_attention(
                q, views["cached_key"], views["cached_value"],
                segment_ids=seg, kv_segment_ids=kv_seg, q_positions=q_slots,
            )
        ckv, kpe = views["cached_ckv"], views["cached_kpe"]
        logits = jnp.einsum("bthr,bsr->bhts", q[..., :6], ckv) + jnp.einsum(
            "bthd,bsd->bhts", q[..., 6:], kpe
        )
        mask = attention_mask(
            q.shape[1], ckv.shape[1], segment_ids=seg,
            kv_segment_ids=kv_seg, q_positions=q_slots,
        )
        probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
        return jnp.einsum("bhts,bsr->bthr", probs, ckv)

    return attend


class Reader(nn.Module):
    """Appends, then runs ``attend`` over ``q`` under the store's bounds.
    With ``attend`` None it answers the rungs the program took: per
    query of a row it read, the length and the row count of the views
    it was handed (0 in a row it did not read)."""

    cfg: Cfg
    attend: Any = None
    q: Any = None

    @nn.compact
    def __call__(self, new, segment_ids):
        read, seg, q_slots = kv_store.append(
            self, self.cfg, new, segment_ids
        )
        t = seg.shape[1]
        if self.attend is None:
            return read(
                lambda views, kv_seg, _: jnp.broadcast_to(
                    jnp.asarray(kv_seg.shape[::-1]), (kv_seg.shape[0], t, 2)
                )
            )
        return read(
            lambda views, kv_seg, rows: self.attend(views, kv_seg, *rows),
            (self.q, seg, q_slots),
        )


@functools.lru_cache(maxsize=None)
def reader(store, layout, b, t, whole=False, probe=False):
    """One jitted program a shape, shared by the cases of a test:
    ``Reader`` over ``config2(store, b)``, attending (``probe``:
    answering its rungs). ``whole`` keeps apart the program that is only
    ever traced under ``whole_rows``: jit does not see a patched
    ladder."""
    cfg = config2(store, b)
    model = Reader(cfg) if probe else Reader(
        cfg, attention_over(layout), queries(layout, b, t)
    )
    return jax.jit(
        lambda cache, new, seg: model.apply(
            {"cache": cache}, new, seg, mutable=["cache"]
        )
    )


def took(probe, cache, new, segment_ids):
    """([key rungs], [row rungs]) the program of ``probe`` took, over the
    rows it read, and the rows it did not."""
    out = np.asarray(probe(cache, new, segment_ids)[0])
    unread = np.flatnonzero((out == 0).all(axis=(1, 2)))
    read = np.delete(out, unread, axis=0)
    return (
        np.unique(read[..., 0]).tolist(),
        np.unique(read[..., 1]).tolist(),
        unread.tolist(),
    )


def filled(cfg, layout, cursors, b=B, t=T2):
    """A cache whose ``b`` rows hold random tokens below ``cursors`` (a
    scalar for the scalar store, else one per row): every written slot
    carries segment 1, every other 0; paged rows own private pages in
    row order after reserved page 0. And a call's ``t`` new tokens."""
    new = {
        n: x.astype(cfg.dtype) for n, x in tokens(layout, t, 0, b).items()
    }
    cache = Reader(cfg).init(jax.random.key(0), new, None)["cache"]
    per_row = np.broadcast_to(np.asarray(cursors), (b,))
    written = np.arange(S2)[None, :] < per_row[:, None]  # [B, S2]
    out = {}
    for i, (name, leaf) in enumerate(sorted(cache.items())):
        kind = kv_store.role(name).kind
        key = jax.random.fold_in(jax.random.key(7), i)
        if kind == kv_store.PAGE and leaf.dtype == jnp.int8:
            out[name] = jax.random.randint(key, leaf.shape, -127, 128, jnp.int8)
        elif kind == kv_store.PAGE:
            out[name] = jax.random.normal(key, leaf.shape, leaf.dtype)
        elif kind == kv_store.SCALE:
            out[name] = jax.random.uniform(
                key, leaf.shape, leaf.dtype, 0.005, 0.02
            )
        elif kind == kv_store.SEGMENT and cfg.kv_page:
            arena = np.zeros(leaf.shape, np.int32)
            arena[1:] = written.reshape(b * PER_ROW2, PAGE)
            out[name] = jnp.asarray(arena)
        elif kind == kv_store.SEGMENT:
            out[name] = jnp.asarray(written, jnp.int32)
        elif kind == kv_store.TABLE:
            out[name] = 1 + jnp.arange(
                b * PER_ROW2, dtype=jnp.int32
            ).reshape(b, PER_ROW2)
        else:
            assert kind == kv_store.CURSOR
            out[name] = jnp.asarray(cursors, jnp.int32)
    return out, new


def cursors_for(store, live):
    """Cursors whose longest row holds ``live`` slots after a T2 block;
    the other rows of a per-row store sit lower."""
    top = live - T2
    return top if store == "scalar" else [max(top - 200, 0), top, 7]


def whole_rows(monkeypatch):
    """Both ladders at one rung: every cached call reads every slot of
    every row, what the bounds are held against."""
    monkeypatch.setattr(kv_store, "key_ladder", lambda s, page=0: (s,))
    monkeypatch.setattr(kv_store, "row_ladder", lambda b: (b,))


@pytest.mark.parametrize(
    "store,layout,live,rows,t",
    [
        pytest.param(store, layout, live, None, T2, id=f"{store}-{layout}-{name}")
        for store in STORES
        for layout in LAYOUTS
        for name, live in zip(
            ["under", "at", "over", "end"], [RUNG - 1, RUNG, RUNG + 1, S2]
        )
    ]
    + [
        # A pool with a row ladder: dead rows by where they sit.
        pytest.param(store, layout, RUNG + 1, rows, t, id=f"{store}-{layout}-{rows}-t{t}")
        for store in STORES[1:]
        for layout in LAYOUTS
        for rows in LIVE_ROWS
        for t in (1, 4)
    ],
)
def test_attention_over_the_live_prefix_is_attention_over_the_row(
    store, layout, live, rows, t, monkeypatch
):
    if rows is None:  # every row live, a pool too narrow for a row rung
        b, seg, read = B, None, np.arange(B)
        cursors = cursors_for(store, live)
        want_rung = {RUNG - 1: RUNG, RUNG: RUNG, RUNG + 1: S2, S2: S2}[live]
        want = ([want_rung], [B], [])
    else:
        # Dead rows' cursors have run on to the end of the row; the live
        # ones sit apart, the longest past the middle rung.
        alive, k = LIVE_ROWS[rows]
        b, read = B8, rows_read(alive)
        seg = jnp.asarray(np.repeat(np.asarray(alive)[:, None], t, 1), jnp.int32)
        cursors = [
            live - t - 300 * sum(alive[:i]) if a else S2
            for i, a in enumerate(alive)
        ]
        # Past the middle key rung, or an eighth of the pool whole.
        want = ([S2], [k], sorted(set(range(b)) - set(read.tolist())))
    cache, new = filled(config2(store, b), layout, cursors, b, t)
    assert took(reader(store, layout, b, t, probe=True), cache, new, seg) == want
    bounded, after = reader(store, layout, b, t)(cache, new, seg)
    # Every row, whole: the same store with ladders of one rung.
    whole_rows(monkeypatch)
    whole, after_whole = reader(store, layout, b, t, whole=True)(cache, new, seg)
    assert bounded.shape == whole.shape and bool(jnp.isfinite(whole).all())
    # The live rows' outputs are the whole read's (a dead row inside the
    # row rung averages the keys of whatever rung it is shown, as ever);
    # rows that were not read come back zero.
    alive = np.arange(b) if seg is None else np.flatnonzero(np.asarray(seg)[:, 0])
    assert set(alive) <= set(read.tolist())
    np.testing.assert_allclose(bounded[alive], whole[alive], rtol=2e-6, atol=2e-6)
    assert bool(jnp.isfinite(bounded).all())
    assert not np.asarray(bounded)[want[2]].any()
    for name in after["cache"]:
        np.testing.assert_array_equal(
            after["cache"][name], after_whole["cache"][name]
        )


@pytest.mark.parametrize(
    "store,layout,rows,t",
    [pytest.param(store, "kv", None, T2, id=store) for store in STORES[1:]]
    + [
        pytest.param(store, layout, rows, t, id=f"{store}-{layout}-{rows}-t{t}")
        for store in STORES[1:]
        for layout in LAYOUTS
        for rows in LIVE_ROWS
        for t in (1, 4)
    ],
)
def test_dead_rows_at_the_end_of_the_row_do_not_choose_the_rung(
    store, layout, rows, t
):
    """A pool's done rows keep stepping and their cursors run on to
    ``max_seq_len``; they step with segment id 0, the key rung is the
    live rows' and the row rung holds the live rows and no more (an
    eighth of the pool is read whole). With ids of 1 they would count:
    the top rung of both ladders."""
    if rows is None:
        b, alive, k = B, [0, 1, 0], B
    else:
        b, (alive, k) = B8, LIVE_ROWS[rows]
    cursors = [2600 - 100 * sum(alive[:i]) if a else S2 for i, a in enumerate(alive)]
    cache, new = filled(config2(store, b), layout, cursors, b, t)
    dead = jnp.asarray(np.repeat(np.asarray(alive)[:, None], t, 1), jnp.int32)
    unread = sorted(set(range(b)) - set(rows_read(alive).tolist()))
    probe = reader(store, layout, b, t, probe=True)
    assert took(probe, cache, new, dead) == (
        [S2 if k < b else RUNG if any(alive) else 2048], [k], unread
    )
    assert took(probe, cache, new, None) == (
        [S2 if not all(alive) else RUNG], [b], []
    )
    least = kv_store.row_ladder(b)[0]
    assert took(probe, cache, new, jnp.zeros((b, t), jnp.int32)) == (
        [S2 if least < b else 2048], [least], list(range(least, b))
    )


@pytest.mark.parametrize("store", STORES[:3])
def test_the_hosts_rung_is_the_programs(store):
    """``attended_pair``, by which the scheduler counts what the device
    read, names the rungs the program's switch took: at every live
    length around every key rung, and at every count of live rows of a
    pool."""
    cfg = config2(store)
    assert kv_store.key_ladder(S2, cfg.kv_page) == (2048, RUNG, S2)
    step = reader(store, "latent", B, T2, probe=True)
    lives = sorted(
        {T2, S2} | {r + d for r in (2048, RUNG, RUNG + 300) for d in (-1, 0, 1)}
    )
    for live in lives:
        cache, new = filled(cfg, "latent", cursors_for(store, live))
        keys, rows, unread = took(step, cache, new, None)
        k, length = kv_store.attended_pair(cfg, B, B, live)
        assert (rows, keys, unread) == ([k], [length], []), live
    if store == "scalar":
        return  # one row rung: every row is read, above
    cfg = config2(store, B8)
    assert kv_store.row_ladder(B8) == (2, B8)
    cache, new = filled(cfg, "latent", [2600] * B8, B8, 1)
    pool_step = reader(store, "latent", B8, 1, probe=True)
    for n in range(B8 + 1):
        alive = np.random.default_rng(n).permutation(B8) < n
        seg = jnp.asarray(alive[:, None], jnp.int32)
        keys, rows, unread = took(pool_step, cache, new, seg)
        k, length = kv_store.attended_pair(cfg, B8, n, 2601 if n else 0)
        assert (rows, keys) == ([k], [length]), n
        assert len(unread) == B8 - k and k == (2 if n <= 2 else B8)
        assert length == (S2 if n <= 2 else RUNG)


def case_branches(text):
    """Branches of the one ``stablehlo.case`` of a lowered program, each
    as its own text and that of every function it calls (a pool's
    branches are calls: ``kv_store._read_rows``); [] without a case."""
    import re

    case = re.findall(
        r'"stablehlo\.case"\(.*?^\s*\}\) : \(tensor<i32>\)', text, re.M | re.S
    )
    assert len(case) <= 1
    funcs = dict(re.findall(
        r"^  func\.func private @([\w.]+)(\(.*?^  \})$", text, re.M | re.S
    ))

    def inlined(part, seen=()):
        called = set(re.findall(r"call @([\w.]+)\(", part)) - set(seen)
        return part + "".join(
            inlined(funcs[name], (*seen, *called)) for name in sorted(called)
        )

    if not case:
        return []
    return [inlined(b) for b in re.split(r"^\s*\}, \{$", case[0], flags=re.M)]


@pytest.mark.parametrize("b", [1, 4, 8, 32, 64])
def test_the_row_ladder_is_a_rule_of_the_pool(b):
    """B/8 where it is whole, then B; the host's twin picks the shortest
    rung that holds the live rows, and reads an eighth of the pool whole.
    A program under ``[B]`` cursors holds a branch for the eighth and
    one a key rung for the whole pool, the whole pool's last, and
    those hold no ordering, no gather of the queries and no scatter (the
    program it was before the row ladder); a scalar cursor's program has
    the key rungs alone."""
    want = {1: (1,), 4: (4,), 8: (1, 8), 32: (4, 32), 64: (8, 64)}[b]
    assert kv_store.row_ladder(b) == want
    keys = kv_store.key_ladder(S2, PAGE)
    for n in range(b + 1):
        k, length = kv_store.attended_pair(config2("paged", b), b, n, 2100)
        assert k in want and k >= n and not any(n <= r < k for r in want)
        assert length == (S2 if k < b else RUNG)
    assert kv_store.row_ladder(3) == (3,) and kv_store.row_ladder(12) == (12,)
    assert kv_store.row_ladder(16) == (2, 16) and kv_store.row_ladder(2) == (2,)
    assert kv_store.branch_pairs((8, 64), (2048, 4096)) == [
        (8, 4096), (64, 2048), (64, 4096)
    ]
    # A row of one key rung keeps one row rung, and so no switch.
    assert kv_store.pool_ladders(S2, PAGE, b) == (want, keys)
    assert kv_store.pool_ladders(2048, PAGE, b) == ((b,), (2048,))
    for store in ("paged", "scalar"):
        cfg = config2(store, b)
        new = tokens("kv", 1, 0, b)
        cache = jax.eval_shape(
            lambda: Reader(cfg).init(jax.random.key(0), new, None)["cache"]
        )
        branches = case_branches(
            jax.jit(
                lambda cache, new: Reader(
                    cfg, attention_over("kv"), queries("kv", b, 1)
                ).apply({"cache": cache}, new, None, mutable=["cache"])
            ).lower(cache, new).as_text()
        )
        rungs = want if store == "paged" else (b,)
        assert len(branches) == len(rungs) - 1 + len(keys)
        for i, part in enumerate(branches):
            pool_wide = i >= len(rungs) - 1
            # One ordering a call, outside the switch; the scatter back
            # to the pool's width in the branches that read fewer rows.
            assert "stablehlo.sort" not in part
            assert ("stablehlo.scatter" not in part) == pool_wide, (store, i)


def test_the_ladder_is_a_rule_of_the_row_and_the_page():
    assert kv_store.key_ladder(16384, 16) == (2048, 4096, 8192, 16384)
    assert kv_store.key_ladder(8192, 16) == (2048, 4096, 8192)
    assert kv_store.key_ladder(4096, 16) == (2048, 4096)
    # One rung, and so no switch, under 4096 slots; no rung under 2048,
    # none that is not whole pages or does not divide the row.
    assert kv_store.key_ladder(2048, 16) == (2048,)
    assert kv_store.key_ladder(S) == (S,)
    assert kv_store.key_ladder(4096) == (2048, 4096)
    assert kv_store.key_ladder(6144, 16) == (3072, 6144)
    assert kv_store.key_ladder(8192, 768) == (8192,)
    text = jax.jit(
        lambda cache, new: Store(Cfg()).apply(
            {"cache": cache}, new, None, mutable=["cache"]
        )
    ).lower(fresh(Cfg(), "kv"), tokens("kv", 1, 0)).as_text()
    assert "case" not in text and "cond" not in text


# ---- the ring: a window layer's last W keys, whatever the row holds ----

W = 8
RING_FEAT = {"ring_key": (2, 4), "ring_value": (2, 4)}


class Ring(nn.Module):
    cfg: Cfg

    @nn.compact
    def __call__(self, new, segment_ids):
        read, seg, q_slots = kv_store.ring_append(
            self, self.cfg, new, segment_ids, W
        )
        views, kv_seg, kv_slots = read(lambda v, s, p: (v, s, p))
        return views, seg, kv_seg, kv_slots, q_slots


def ring_tokens(t, seed):
    return {
        name: jax.random.normal(
            jax.random.fold_in(jax.random.key(seed), i), (B, t, *feat)
        ).astype(jnp.bfloat16)
        for i, (name, feat) in enumerate(RING_FEAT.items())
    }


def visible(out, row, query):
    """{logical slot: key} that query ``query`` of this call may attend
    in ``row``: the store's own mask, ``attention_mask``'s rule."""
    views, seg, kv_seg, kv_slots, q_slots = out
    q = int(np.asarray(q_slots)[row if q_slots.shape[0] > 1 else 0, query])
    behind = q - np.asarray(kv_slots)[row]
    ok = (
        (behind >= 0) & (behind < W)
        & (np.asarray(kv_seg)[row] == int(np.asarray(seg)[row, query]))
    )
    keys = np.asarray(views["ring_key"].astype(jnp.float32))[row]
    seen = {}
    for j in np.flatnonzero(ok):
        slot = int(np.asarray(kv_slots)[row, j])
        assert slot not in seen, "a key shows once"
        seen[slot] = keys[j]
    return seen


@pytest.mark.parametrize("cursor", ["scalar", "rows"])
def test_the_ring_shows_each_query_its_window_and_nothing_else(cursor):
    """Blocks of 5 (a prefill), single steps, a block of 4 (wider than
    what is left of a lap), a block of 11 (wider than the ring) and more
    steps: three laps of a ring of 8. Every query sees exactly the
    tokens at the last 8 logical slots up to its own, with their values,
    under a scalar cursor (a row twin) and under per-row cursors that
    start apart (a pool)."""
    cfg = Cfg(kv_page=PAGE if cursor == "rows" else 0, kv_pages=2)
    cache = jax.tree_util.tree_map(
        jnp.zeros_like,
        Ring(cfg).init(jax.random.key(0), ring_tokens(1, 0), None)["cache"],
    )
    assert cache["ring_key"].shape == (B, W, 2, 4), "not max_seq_len, not pages"
    assert cache["cache_index"].shape == ((B,) if cursor == "rows" else ())
    start = np.array([0, 3, 9]) if cursor == "rows" else np.zeros(B, int)
    if cursor == "rows":
        cache["cache_index"] = jnp.asarray(start, jnp.int32)
    written = [dict() for _ in range(B)]  # logical slot -> key, per row
    at = start.copy()
    for i, t in enumerate([5, 1, 1, 1, 4, 1, 11, 1, 1]):
        new = ring_tokens(t, 10 + i)
        out, mutated = Ring(cfg).apply({"cache": cache}, new, None, mutable=["cache"])
        cache = mutated["cache"]
        assert out[0]["ring_key"].shape[1] == kv_store.ring_keys(W, t)
        keys = np.asarray(new["ring_key"].astype(jnp.float32))
        for row in range(B):
            for j in range(t):
                written[row][int(at[row]) + j] = keys[row, j]
            for j in range(t):
                q = int(at[row]) + j
                want = {s: k for s, k in written[row].items() if 0 <= q - s < W}
                got = visible(out, row, j)
                assert sorted(got) == sorted(want), (i, row, j)
                for s in want:
                    np.testing.assert_array_equal(got[s], want[s])
        at += t
    assert np.asarray(cache["cache_index"]).tolist() == (
        at.tolist() if cursor == "rows" else int(at[0])
    )


def test_padding_and_done_rows_are_not_written_to_the_ring():
    """A chunk padded on the right (segment 0) leaves the ring at the real
    tokens; the cursor is then set back to them, as the chunk program
    does, and the next chunk's queries see the real tokens alone. A row
    that steps with segment 0 (a pool's done row) writes nothing."""
    cfg = Cfg()
    cache = jax.tree_util.tree_map(
        jnp.zeros_like,
        Ring(cfg).init(jax.random.key(0), ring_tokens(1, 0), None)["cache"],
    )
    first = ring_tokens(6, 1)
    seg = jnp.asarray([[1, 1, 1, 1, 0, 0]] * B, jnp.int32)
    _, mutated = Ring(cfg).apply({"cache": cache}, first, seg, mutable=["cache"])
    cache = dict(mutated["cache"])
    assert int(jnp.sum(cache["ring_segment"] > 0)) == 4 * B
    cache["cache_index"] = jnp.asarray(4, jnp.int32)
    out, mutated = Ring(cfg).apply({"cache": cache}, ring_tokens(3, 2), None, mutable=["cache"])
    assert sorted(visible(out, 1, 2)) == [0, 1, 2, 3, 4, 5, 6]
    done = jnp.asarray([[1], [0], [1]], jnp.int32)
    before = mutated["cache"]
    _, after = Ring(cfg).apply({"cache": before}, ring_tokens(1, 3), done, mutable=["cache"])
    for name in ("ring_key", "ring_value", "ring_slot", "ring_segment"):
        assert bool(jnp.all(after["cache"][name][1] == before[name][1])), name
        assert not bool(jnp.all(after["cache"][name][0] == before[name][0])), name


def test_ring_leaves_are_per_slot_and_the_hosts_count_is_the_programs():
    for name in ("ring_key", "ring_value", "ring_slot", "ring_segment"):
        r = kv_store.role(name)
        assert r.kind == kv_store.RING and r.per_slot and not r.in_arena
    assert kv_store.role("kda_state").per_slot and not kv_store.role("cached_key").per_slot
    assert {k: d.reason for k, d in kv_store.DECLINES.items()} == {
        kv_store.STATE: "state_layers", kv_store.RING: "window_layers"
    }
    assert (kv_store.ring_keys(512, 1), kv_store.ring_keys(512, 512)) == (512, 1024)
    cache = Ring(Cfg()).init(jax.random.key(0), ring_tokens(1, 0), None)["cache"]
    stacked = {"layers": jax.tree_util.tree_map(lambda x: jnp.stack([x] * 3), dict(cache))}
    assert kv_store.ring_layers({"a": dict(cache), "b": dict(cache)}) == (2, W)
    assert kv_store.ring_layers(stacked) == (3, W)
    plain = Store(Cfg()).init(jax.random.key(0), tokens("kv", 1, 0), None)["cache"]
    assert kv_store.ring_layers(plain) == (0, 0)
    with pytest.raises(ValueError, match="rank 3"):
        Ring(Cfg()).init(jax.random.key(0), {"ring_key": jnp.zeros((B, 1, 4))}, None)
    # One static length a program: no switch, whatever max_seq_len is.
    big = Cfg(max_seq_len=16384)
    text = jax.jit(
        lambda cache, new: Ring(big).apply({"cache": cache}, new, None, mutable=["cache"])
    ).lower(cache, ring_tokens(1, 0)).as_text()
    assert "case" not in text and "16384" not in text
