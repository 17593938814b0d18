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
        return kv_store.append(self, self.cfg, new, segment_ids)


def tokens(layout, t, seed):
    """[B, t, *feat] per leaf, already bf16 so a bf16 store is exact."""
    return {
        name: jax.random.normal(
            jax.random.fold_in(jax.random.key(seed), i), (B, t, *feat)
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
