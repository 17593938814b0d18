"""Paged KV pool: fixed-size page arena + per-slot page tables.

The contiguous ``SlotPool`` (tpufw.infer.slots) charges every occupied
slot a full ``[cache_len]`` KV row, so HBM — not compute — caps
concurrent rows per chip, and identical prompt prefixes are prefilled
and stored once PER ROW. This module keeps the slot scheduler's whole
zero-recompile contract (occupancy, cursors, and now page-table churn
are all DATA) while storing KV in a global arena of ``kv_pages`` pages
of ``kv_page`` slots each:

- the STORE (``tpufw.ops.kv_store``, called by every model) owns the
  arena + table + gather/scatter reads and every leaf's role — the
  cache leaves just have a different shape, so ``_decode_steps_jit`` is
  reused verbatim. A step gathers ``table[rows, :L/page]``, the live
  prefix of the live rows and not every slot's whole table: L is a rung
  of the store's ladder of key lengths and the K rows a rung of its
  ladder of row counts, both chosen inside the program from the rows
  that are not done (``PagedSlotPool.attended_keys`` is the same rule
  for the scheduler's count);
- this module owns moving rows in and out, and the allocator; it asks
  ``kv_store.role()`` what each leaf is: ``_paged_insert_jit``
  scatters a B=1 contiguous prefilled row into the slot's pages,
  ``PagedSlotPool.release_slot`` zeroes the table row (stale writes
  from a done-but-stepped row then land in reserved page 0, never in a
  reused page) and returns the pages to the host-side
  ``PageAllocator``;
- prefix sharing rides on top: ``PrefixCache`` (tpufw.infer.prefix)
  maps full-page token chunks to resident pages, ``prefill_shared``
  gathers the shared pages into a fresh row cache and prefills ONLY
  the suffix. Only full pages strictly before the row's first write
  slot are shared, so copy-on-write is structural — divergence lands
  in private pages, never needs a device copy.

Static-shape discipline and retrace budget: ``decode_steps`` stays ONE
program forever. Insert/attach/suffix-prefill programs are keyed by
(prompt-length, shared-page-count) — bounded by the traffic's distinct
prompt shapes, paid at admission (the same place the contiguous path
pays its prefill-bucket programs), never per decode step.

int8 KV (``cfg.kv_quant == "int8"``): arenas are int8 with per-token
fp32 scales stored page-structured ``[kv_pages, kv_page]``. Decode
tokens are quantized by the store at append; prompt tokens are
quantized HERE at insert (prefill itself runs full-precision through
the contiguous row cache).
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpufw.infer.generate import _model_apply, split_prefill_keys
from tpufw.infer.prefix import PrefixCache
from tpufw.infer.sampling import sample_token
from tpufw.infer.slots import (
    SlotPool,
    _retire_jit,
    _track_seen,
    per_slot_decline,
    reject_state,
)
from tpufw.obs import trace as obs_trace
from tpufw.ops.kv_store import (
    CURSOR, PAGE, SCALE, SEGMENT, TABLE, attended_slots, ring_keys, role,
)
from tpufw.ops.kv_store import leaf_name as _leaf_name
from tpufw.ops.quant import dequantize_kv, quantize_kv

# Trace-time counters, same contract as tpufw.infer.slots.TRACE_COUNTS:
# bumped once per (re)trace so tests can pin the retrace budget.
TRACE_COUNTS: Dict[str, int] = {
    "paged_insert": 0, "clear_table": 0, "prefix_attach": 0,
    "suffix_prefill": 0, "page_export": 0, "page_splice": 0,
    "prefill_chunk": 0, "page_import": 0,
}


def _flatten_with_names(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = tuple(jax.tree_util.keystr(p) for p, _ in flat)
    names = tuple(_leaf_name(p) for p, _ in flat)
    leaves = [leaf for _, leaf in flat]
    return paths, names, leaves, treedef


def _collapse_arena(leaf, rank):
    """[*stack, n_pages, page, *feat] -> [stacks, n_pages, page, *feat]
    (stacks = nn.scan layer axes collapsed; 1 when unscanned)."""
    return leaf.reshape((-1,) + leaf.shape[leaf.ndim - rank:])


def _collapse_row(row, rank):
    """[*stack, 1, W, *feat] -> [stacks, W, *feat] (B=1 absorbed)."""
    return row.reshape((-1,) + row.shape[row.ndim - rank + 1:])


def paged_pool_cache(model, params, n_slots: int):
    """Zeroed paged cache for ``model`` (cfg.kv_page > 0) at B=n_slots.

    The paged branch creates its per-row cursor/table as [B] vectors
    directly, so — unlike the contiguous ``pool_cache`` — no axis
    probing or trailing-slot-axis surgery is needed: the model's own
    init shapes ARE the pool shapes. Zeros are safe initial state
    (page 0 reserved, segment 0 everywhere; per-slot state leaves come
    [n_slots, *feat] and zero is a row's empty past)."""

    def init(p):
        toks = jnp.zeros((n_slots, 1), jnp.int32)
        pos = jnp.zeros((n_slots, 1), jnp.int32)
        seg = jnp.ones((n_slots, 1), jnp.int32)
        _, vars_ = model.apply(
            {"params": p}, toks, positions=pos, segment_ids=seg,
            mutable=["cache"],
        )
        return vars_["cache"]

    shapes = jax.eval_shape(init, params)
    tree = jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, l.dtype), shapes
    )
    return {"cache": tree}


def _row_cache_shapes(row_model, params):
    """Abstract B=1 CONTIGUOUS row cache of ``row_model`` (the paged
    model's contiguous twin): the shapes ``prefill_row`` hands back,
    per-slot state leaves included at B=1. One host trace of the whole
    row model — seconds at published widths — and the same for a pool's
    whole life, so a pool asks once (``PagedSlotPool._find_row_shapes``)."""

    def init(p):
        toks = jnp.zeros((1, 1), jnp.int32)
        pos = jnp.zeros((1, 1), jnp.int32)
        seg = jnp.ones((1, 1), jnp.int32)
        _, vars_ = row_model.apply(
            {"params": p}, toks, positions=pos, segment_ids=seg,
            mutable=["cache"],
        )
        return vars_["cache"]

    # Wrapped in the same {"cache": ...} form prefill_row returns, so
    # path alignment against the pool tree lines up leaf-for-leaf.
    return {"cache": jax.eval_shape(init, params)}


class PageAllocator:
    """Host-side free-list + refcounts over the device page arena.

    Page 0 is reserved (the causally-masked junk sink unmapped table
    entries point at) and never enters the free list. A page is free
    iff its row refcount is 0 AND the prefix trie does not hold it."""

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError(
                f"kv_pages={n_pages}: need >= 2 (page 0 is reserved)"
            )
        self.n_pages = int(n_pages)
        # LIFO free list: recently-freed pages are re-used first (their
        # arena lines are warm).
        self.free: List[int] = list(range(n_pages - 1, 0, -1))
        self.refs: Dict[int, int] = {}
        self.held: set = set()
        self.freed_total = 0

    @property
    def n_free(self) -> int:
        return len(self.free)

    @property
    def capacity(self) -> int:
        return self.n_pages - 1

    @property
    def in_use(self) -> int:
        return self.capacity - len(self.free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` free pages with refcount 1, or None (all-or-
        nothing — a partial grab would deadlock two part-admitted
        rows)."""
        # resource: acquires pages
        if n > len(self.free):
            return None
        ids = [self.free.pop() for _ in range(n)]
        for i in ids:
            self.refs[i] = 1
        return ids

    def ref(self, ids: Sequence[int]) -> None:
        for i in ids:
            self.refs[i] = self.refs.get(i, 0) + 1

    def release(self, ids: Sequence[int]) -> int:
        """Drop one row reference per id; free those that hit 0 and are
        not trie-held. Returns the number actually freed."""
        # resource: releases pages
        freed = 0
        for i in ids:
            r = self.refs.get(i, 0) - 1
            if r > 0:
                self.refs[i] = r
            else:
                self.refs.pop(i, None)
                if i not in self.held:
                    self.free.append(i)
                    freed += 1
        self.freed_total += freed
        return freed

    def hold(self, ids: Sequence[int]) -> None:
        self.held.update(int(i) for i in ids)

    def drop(self, ids: Sequence[int]) -> int:
        """Trie eviction path: drop the hold; free ids no row uses."""
        freed = 0
        for i in ids:
            self.held.discard(i)
            if self.refs.get(i, 0) == 0:
                self.free.append(i)
                freed += 1
        self.freed_total += freed
        return freed


@partial(
    jax.jit,
    static_argnames=("names", "scale_src", "page", "quant"),
    donate_argnames=("leaves", "token", "pos", "done", "remaining", "seen"),
)
def _paged_insert_jit(
    leaves, row_leaves, table_row, slot, start, first, pos0, budget,
    token, pos, done, remaining, seen, row_seen,
    *, names, scale_src, page, quant,
):
    """Scatter a B=1 contiguous prefilled row into slot ``slot``'s
    pages. ``table_row`` [per_row] holds the slot's physical page ids
    (0-padded past the row's need); ``start`` (TRACED — shared vs cold
    never retraces) is the first logical slot this row owns: slots
    below it belong to shared prefix pages and are redirected into
    reserved page 0 (harmless duplicate junk) instead of overwriting
    shared content."""
    TRACE_COUNTS["paged_insert"] += 1
    per_row = table_row.shape[0]
    w = per_row * page
    idx = jnp.arange(w)
    off = idx % page
    phys = jnp.where(idx >= start, table_row[idx // page], 0)

    quantized = {}
    if quant:
        for i, name in enumerate(names):
            r = role(name)
            if r.kind == PAGE:
                rr = _collapse_row(row_leaves[i], r.rank)
                quantized[i] = quantize_kv(rr, n_feat=r.rank - 2)

    out = []
    for i, (name, leaf) in enumerate(zip(names, leaves)):
        r = role(name)
        if r.kind == TABLE:
            out.append(leaf.at[..., slot, :].set(table_row))
        elif r.kind == CURSOR:
            out.append(leaf.at[..., slot].set(row_leaves[i]))
        elif r.per_slot:
            # Per-slot state or a window layer's ring: the row's, whole
            # — nothing of the slot's previous occupant survives the
            # insert.
            a = _collapse_arena(leaf, r.rank)  # [stacks, n_slots, *feat]
            row = _collapse_arena(row_leaves[i], r.rank)[:, 0]
            out.append(
                a.at[:, slot].set(row.astype(leaf.dtype)).reshape(leaf.shape)
            )
        else:  # in the arena: pages, their scales, segment ids
            if r.kind == SCALE:
                vals = quantized[scale_src[i]][1]  # [stacks, W] fp32
            elif quant and r.kind == PAGE:
                vals = quantized[i][0]
            else:
                vals = _collapse_row(row_leaves[i], r.rank).astype(leaf.dtype)
            a = _collapse_arena(leaf, r.rank)
            out.append(a.at[:, phys, off].set(vals).reshape(leaf.shape))
    token = token.at[slot].set(first)
    pos = pos.at[slot].set(pos0)
    done = done.at[slot].set(False)
    remaining = remaining.at[slot].set(budget)
    if seen is not None:
        seen = seen.at[slot].set(row_seen[0])
    return tuple(out), token, pos, done, remaining, seen


@partial(jax.jit, donate_argnames=("tables",))
def _clear_tables_jit(tables, slot):
    """Zero slot ``slot``'s page-table row in every layer: a retired
    row's residual writes (done rows keep stepping under static shapes)
    then land in reserved page 0 instead of a page someone else may
    have been handed."""
    TRACE_COUNTS["clear_table"] += 1
    return tuple(t.at[..., slot, :].set(0) for t in tables)


@partial(
    jax.jit,
    static_argnames=("names", "scale_of", "page", "quant"),
    donate_argnames=("row_leaves",),
)
def _attach_shared_jit(
    row_leaves, pool_leaves, ids, *, names, scale_of, page, quant,
):
    """Gather ``ids``' pages out of the arena into logical slots
    [0, len(ids)*page) of a zeroed B=1 contiguous row cache (dequantized
    in int8 mode — suffix prefill attends full-precision), segment 1,
    cursor = shared length. Programs are keyed by the shared-page
    count. Inputs/outputs ride in POOL leaf order; entries with no row
    counterpart (page_table, scales) pass None through."""
    TRACE_COUNTS["prefix_attach"] += 1
    n = ids.shape[0]
    length = n * page
    out = []
    for i, name in enumerate(names):
        row, r = row_leaves[i], role(name)
        if row is None:
            out.append(None)
        elif r.kind == CURSOR:
            out.append(jnp.full(row.shape, length, row.dtype))
        elif r.kind in (PAGE, SEGMENT):
            a = _collapse_arena(pool_leaves[i], r.rank)
            g = a[:, ids]  # [stacks, n, page, *feat]
            if quant and r.kind == PAGE:
                sa = _collapse_arena(pool_leaves[scale_of[i]], 2)
                g = dequantize_kv(g, sa[:, ids], row.dtype)
            g = g.reshape((g.shape[0], length) + g.shape[3:])
            rr = _collapse_row(row, r.rank)
            out.append(
                rr.at[:, :length].set(g.astype(rr.dtype)).reshape(row.shape)
            )
        else:
            # Per-slot state: shared pages do not determine it
            # (PagedSlotPool._match_prefix declines first).
            raise ValueError(f"prefix attach: no rule for {name!r}")
    return tuple(out)


@partial(jax.jit, static_argnames=("names",))
def _export_pages_jit(leaves, ids, *, names):
    """Gather pages ``ids`` out of every bundle-traveling arena leaf,
    RAW (int8 codes + their scales ship as stored — no dequantize, so
    a splice on the receiving arena is bit-identical storage and the
    wire stays ~4x cheaper in int8 mode). NOT donating: the arena
    stays live — export observes, it never consumes. Programs are
    keyed by the page count, same budget class as prefix attach."""
    TRACE_COUNTS["page_export"] += 1
    out = []
    for name, leaf in zip(names, leaves):
        r = role(name)
        if r.in_arena:
            a = _collapse_arena(leaf, r.rank)
            out.append(a[:, ids])  # [stacks, n, page, *feat]
    return tuple(out)


@partial(jax.jit, static_argnames=("names",), donate_argnames=("leaves",))
def _import_pages_jit(leaves, page_arrays, ids, *, names):
    """Scatter spilled pages back into arena pages ``ids`` — the
    donating twin of ``_export_pages_jit`` and exactly the page-payload
    half of ``_splice_pages_jit`` (no table row, no cursors: trie pages
    belong to no slot, rows find them through the prefix match). Raw
    stores both ways means spill -> restore is bit-identical storage.
    Programs are keyed by the page count, same budget class as
    export."""
    TRACE_COUNTS["page_import"] += 1
    k = 0
    out = []
    for name, leaf in zip(names, leaves):
        r = role(name)
        if not r.in_arena:
            out.append(leaf)
            continue
        a = _collapse_arena(leaf, r.rank)
        vals = page_arrays[k].astype(leaf.dtype)
        out.append(a.at[:, ids].set(vals).reshape(leaf.shape))
        k += 1
    return tuple(out)


@partial(
    jax.jit,
    static_argnames=("names",),
    donate_argnames=(
        "leaves", "token", "pos", "done", "remaining", "seen",
    ),
)
def _splice_pages_jit(
    leaves, page_arrays, ids, table_row, slot, cache_idx,
    first, pos0, budget, done0,
    token, pos, done, remaining, seen, row_seen,
    *, names,
):
    """Scatter a migrated bundle's pages into freshly allocated arena
    pages ``ids`` and point slot ``slot``'s table row at them. The
    page-table indirection is what makes migration invisible to the
    math: physical ids differ per replica, but the gather reconstructs
    the same logical row, so greedy decode after a splice is bit-equal
    to the never-migrated run — and the cache shapes are untouched, so
    ``decode_steps`` stays the one program it always was."""
    TRACE_COUNTS["page_splice"] += 1
    k = 0
    out = []
    for name, leaf in zip(names, leaves):
        r = role(name)
        if r.kind == TABLE:
            out.append(leaf.at[..., slot, :].set(table_row))
            continue
        if r.kind == CURSOR:
            out.append(leaf.at[..., slot].set(cache_idx))
            continue
        if not r.in_arena:
            # splice_slot refuses a pool with per-slot state first.
            raise ValueError(
                f"page splice: no bundle carries {name!r} ({r.kind}), and "
                "an untouched leaf would leak the previous occupant's"
            )
        a = _collapse_arena(leaf, r.rank)
        vals = page_arrays[k].astype(leaf.dtype)
        out.append(a.at[:, ids].set(vals).reshape(leaf.shape))
        k += 1
    token = token.at[slot].set(first)
    pos = pos.at[slot].set(pos0)
    done = done.at[slot].set(done0)
    remaining = remaining.at[slot].set(budget)
    if seen is not None:
        seen = seen.at[slot].set(row_seen)
    return tuple(out), token, pos, done, remaining, seen


@partial(
    jax.jit,
    static_argnames=("model", "sampling", "eos_id"),
    donate_argnames=("cache",),
)
def _suffix_prefill_jit(
    model, params, cache, suffix, prompt_full, start_pos, rng,
    *, sampling, eos_id,
):
    """Prefill ONLY the unshared suffix over an attached row cache and
    sample the first token with ``split_prefill_keys``' first key — the
    exact key a cold ``prefill_row`` of the full prompt would use, so
    shared and cold admissions draw identical sample streams."""
    TRACE_COUNTS["suffix_prefill"] += 1
    b, t = suffix.shape
    seg = jnp.ones((b, t), jnp.int32)
    positions = start_pos + jnp.arange(t)[None, :]
    apply = _model_apply(model, params)
    logits, cache = apply(cache, suffix, positions, seg)
    seen = None
    if _track_seen(sampling):
        # Repetition-penalty presence mask over the FULL prompt (the
        # shared tokens count even though they were never re-run).
        vocab = logits.shape[-1]
        seen = (
            jnp.zeros((b, vocab), bool)
            .at[jnp.arange(b)[:, None], prompt_full]
            .set(True)
        )
    first_rng, _ = split_prefill_keys(rng, 1)
    first = sample_token(logits[:, -1, :], sampling, first_rng, seen)
    if seen is not None:
        seen = seen.at[jnp.arange(b), first].set(True)
    done = jnp.zeros((b,), bool) if eos_id is None else first == eos_id
    return cache, first, done, seen


@partial(
    jax.jit,
    static_argnames=(
        "row_model", "sampling", "eos_id", "paths", "names",
        "scale_src", "page", "quant",
    ),
    donate_argnames=("leaves", "row_cache", "seen_row"),
)
def _prefill_chunk_jit(
    leaves, row_cache, params, tokens, chunk_ids, start, n_real,
    is_final, rng, seen_row,
    *, row_model, sampling, eos_id, paths, names, scale_src, page,
    quant,
):
    """Advance one in-flight chunked prefill by ONE page-aligned chunk:
    run ``tokens`` (right-padded to a whole number of pages) through
    the contiguous row cache at logical offset ``start``, then scatter
    the freshly written window straight into the chunk's arena pages
    ``chunk_ids``. Programs are keyed by (chunk width, quant) — mid
    chunks all share the ``chunk_pages`` program and tails reuse one
    program per page-granular width, so chunk-COUNT variation and page
    churn never retrace (``start``/``n_real``/``is_final``/``rng`` are
    all traced).

    Parity with monolithic prefill holds per query: every apply
    attends the row cache's live prefix (``start + width`` slots, rounded
    up to a rung of tpufw.ops.kv_store's ladder, chosen inside this
    program from the row's cursor; the slots past it are the ones the
    causal mask hides) under the causal + segment mask, padded tail
    slots carry segment 0 (their logits weights underflow to an exact
    0.0), and the window scatter quantizes per token — identical values
    to a whole-row insert. A chunk at a lower rung than the monolithic
    pass reduces over fewer (all-zero-weight) slots: equal up to
    reduction order. Sampling runs every chunk (one
    program), but only the final chunk's draw is kept by the host; the
    key is ``split_prefill_keys``' first key, the exact key a cold
    ``prefill_row`` of the full prompt would use.

    The model leaves cursor = start + width after a padded tail; the
    row's cache_index leaves are rewritten to ``start + n_real`` here
    so finalize (``_paged_insert_jit`` reading the row leaf) sees the
    true prompt length."""
    TRACE_COUNTS["prefill_chunk"] += 1
    b, width = tokens.shape
    in_win = jnp.arange(width)
    valid = in_win < n_real
    seg = valid.astype(jnp.int32)[None, :]
    positions = start + in_win[None, :]
    apply = _model_apply(row_model, params)
    logits, row_cache = apply(row_cache, tokens, positions, seg)
    row_paths, row_names, row_leaves, row_treedef = _flatten_with_names(
        row_cache
    )
    row_leaves = [
        jnp.full(l.shape, start + n_real, l.dtype)
        if role(n).kind == CURSOR else l
        for n, l in zip(row_names, row_leaves)
    ]
    if seen_row is not None:
        # Prompt tokens enter the presence mask BEFORE the (possibly
        # final) sample, matching _suffix_prefill_jit's ordering.
        seen_row = seen_row.at[0, tokens[0]].max(valid)
    last = jax.lax.dynamic_slice_in_dim(logits, n_real - 1, 1, axis=1)
    first_rng, _ = split_prefill_keys(rng, 1)
    first = sample_token(last[:, 0, :], sampling, first_rng, seen_row)
    if seen_row is not None:
        # Only the kept (final-chunk) draw marks the mask.
        seen_row = seen_row.at[jnp.arange(b), first].max(is_final)
    done0 = (
        jnp.zeros((b,), bool) if eos_id is None else first == eos_id
    )
    # Pool and row trees flatten to identical path strings (same
    # module tree, different leaf shapes); the row simply lacks
    # page_table/scale leaves, so .get() -> None for those.
    row_map = dict(zip(row_paths, row_leaves))
    aligned = [row_map.get(p) for p in paths]
    off = in_win % page
    # Padded tail slots scatter into reserved page 0 — the same junk
    # sink unmapped table entries read through.
    phys = jnp.where(valid, chunk_ids[in_win // page], 0)

    def window(i, rank):
        rr = _collapse_row(aligned[i], rank)
        return jax.lax.dynamic_slice_in_dim(rr, start, width, axis=1)

    quantized = {}
    if quant:
        for i, name in enumerate(names):
            r = role(name)
            if r.kind == PAGE:
                quantized[i] = quantize_kv(
                    window(i, r.rank), n_feat=r.rank - 2
                )
    out = []
    for i, (name, leaf) in enumerate(zip(names, leaves)):
        r = role(name)
        if not r.in_arena:
            # finalize owns the pool-side table and cursors; per-slot
            # state stays in the row twin until then (nothing to scatter).
            out.append(leaf)
            continue
        if r.kind == SCALE:
            vals = quantized[scale_src[i]][1]
        elif quant and r.kind == PAGE:
            vals = quantized[i][0]
        else:
            vals = window(i, r.rank).astype(leaf.dtype)
        a = _collapse_arena(leaf, r.rank)
        out.append(a.at[:, phys, off].set(vals).reshape(leaf.shape))
    row_out = jax.tree_util.tree_unflatten(row_treedef, row_leaves)
    return tuple(out), row_out, first, done0, seen_row


@dataclasses.dataclass
class ChunkedPrefill:
    """Host-side cursor of one in-flight chunked prefill: the prompt,
    its contiguous row cache mid-flight, the pages committed so far,
    and the rng the final chunk samples with. Created by
    ``PagedSlotPool.start_chunked``, advanced by ``chunk_step``,
    consumed by ``finalize_chunked`` (or ``abandon_chunked`` on
    preemption — the trie checkpoint keeps every completed full page,
    so a re-admission resumes instead of restarting)."""

    prompt: List[int]
    rng: Any
    chunk_pages: int
    n_total: int  # pages the finished row owns (incl. decode budget)
    row_cache: Any  # None until the first chunk_step attaches it
    seen_row: Any
    cursor: int  # logical slots committed so far (page-aligned)
    page_ids: List[int]
    shared_n: int  # trie-shared pages attached at start
    n_chunks: int = 0
    first: Any = None
    first_int: int = -1
    done0: bool = False

    @property
    def resumed(self) -> bool:
        return self.shared_n > 0

    @property
    def deficit(self) -> int:
        """Pages still to acquire before this prefill can finish —
        admission guards sum this across in-flight chunked prefills so
        two part-admitted rows can never deadlock on the arena."""
        return self.n_total - len(self.page_ids)


@dataclasses.dataclass
class PagedSlotPool(SlotPool):
    """SlotPool whose KV lives in a shared page arena.

    ``decode_steps`` is INHERITED unchanged — paging is internal to the
    model's cache leaves. Insert/retire are replaced by page-aware
    versions, and two host-side owners ride along: ``allocator``
    (free list + refcounts) and ``prefix`` (radix trie; None when
    prefix caching is off). ``row_model`` is the contiguous twin
    (kv_page=0, same max_seq_len) prefill runs through."""

    row_model: Any = None
    page: int = 0
    allocator: Any = None
    prefix: Any = None
    slot_pages: Any = None  # per-slot page ids this row references
    #: Spill-tier callbacks (tpufw.serve.roles wires them to a
    #: tpufw.infer.spill.SpillTier + the TPFB codec; None = no spill).
    #: trie_spill(path_tokens, state) receives an evicted trie page's
    #: export state; trie_restore(path_tokens) -> state | None CONSUMES
    #: the matching spill entry (the pages are back in the arena — a
    #: kept copy would go stale the moment decode appends).
    trie_spill: Any = None
    trie_restore: Any = None
    #: Span sink for the host work done in here (``serve_row_alloc``,
    #: the final chunk's ``serve_device_wait`` and ``serve_fetch``); the
    #: serve scheduler mounts its own tracer after building the pool.
    tracer: Any = obs_trace.NULL
    # Admission-outcome counters for signals()/bench: requests whose
    # trie match (incl. spill restores) covered >= 1 page vs not, and
    # pages moved across the HBM <-> spill boundary.
    prefix_hits: int = 0
    prefix_misses: int = 0
    spill_pages_out: int = 0
    spill_pages_in: int = 0
    #: Why this pool has no prefix trie although one was asked for
    #: ("state_layers": the model keeps per-slot state; "window_layers":
    #: rings). The scheduler counts the admissions declined for it, by
    #: this reason.
    prefix_decline: str = ""
    #: The row twin's B=1 contiguous cache as ShapeDtypeStructs, found
    #: once by ``_find_row_shapes`` when the pool is built; ``_fresh_row``
    #: is the program that fills it with zeros at ``home``.
    row_shapes: Any = None
    #: Times this pool traced its row model for ``row_shapes``: 1 for
    #: its whole life (``tpufw_serve_row_shape_traces_total`` adds it).
    row_shape_traces: int = 0
    _fresh_row: Any = None

    @classmethod
    def create_paged(
        cls,
        model,
        row_model,
        params,
        n_slots: int,
        *,
        sampling,
        pad_id: int = 0,
        eos_id: Optional[int] = None,
        prefix_cache: bool = True,
        allocator: Optional[PageAllocator] = None,
    ) -> "PagedSlotPool":
        cfg = model.cfg
        cache = paged_pool_cache(model, params, n_slots)
        seen = None
        if _track_seen(sampling):
            seen = jnp.zeros((n_slots, cfg.vocab_size), bool)
        if allocator is not None and allocator.n_pages != int(cfg.kv_pages):
            # Shared-allocator mode (speculative draft pool riding the
            # target's arena budget): one page-id space over the two
            # physically separate arenas, so both must be sized alike.
            raise ValueError(
                f"shared allocator covers {allocator.n_pages} pages but "
                f"cfg.kv_pages={cfg.kv_pages}"
            )
        # Shared pages are the arena's K/V alone: a row attached to them
        # would start its state layers from zero and its window layers
        # from an empty ring, silently wrong. No trie, counted.
        declined = per_slot_decline(cache) if prefix_cache else None
        decline = declined.reason if declined else ""
        pool = cls(
            model=model,
            params=params,
            n_slots=n_slots,
            sampling=sampling,
            pad_id=pad_id,
            eos_id=eos_id,
            cache=cache,
            axes=(),
            token=jnp.zeros((n_slots,), jnp.int32),
            pos=jnp.zeros((n_slots,), jnp.int32),
            done=jnp.ones((n_slots,), bool),
            remaining=jnp.zeros((n_slots,), jnp.int32),
            seen=seen,
            row_model=row_model,
            page=int(cfg.kv_page),
            allocator=(
                PageAllocator(int(cfg.kv_pages))
                if allocator is None else allocator
            ),
            prefix=(
                PrefixCache(int(cfg.kv_page))
                if prefix_cache and not decline else None
            ),
            prefix_decline=decline,
            slot_pages=[[] for _ in range(n_slots)],
        )
        pool._find_row_shapes()
        return pool

    def _find_row_shapes(self) -> None:
        """Trace the row model, once, for the shapes of its B=1 row
        cache, and build the one program that fills them with zeros.

        Both are the pool's for life (``row_model``, the parameters'
        structure and ``home`` never change under it), and both belong
        to pool construction: the trace is host seconds and the program
        a backend compile, neither of which an admission may pay. The
        program's output is born committed at ``home`` like the pool's
        own state (SlotPool.__post_init__): the chunk programs see this
        canvas first and their own donated output after, and the two
        must be the same argument to jit. Per-slot state leaves are in
        it at B=1, zero: the row's own state from its first token on,
        never a slot's."""
        self.row_shape_traces += 1
        self.row_shapes = _row_cache_shapes(self.row_model, self.params)
        shapes = self.row_shapes

        def row_zeros():
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes
            )

        self._fresh_row = (
            jax.jit(row_zeros, out_shardings=self.home).lower().compile()
        )

    def attended_keys(
        self, calls, chunk: bool = False, width: int = 1
    ) -> Tuple[int, int]:
        """(key slots read, key slots of the whole rows) summed over
        cached calls of ``width`` tokens a row, each the slots its live
        rows hold, the call's own tokens included (none: no row was
        live): decode steps and verify blocks of the pool's model, every
        slot a row, or, with ``chunk``, prefill chunks of the row twin,
        one row under a scalar cursor. The store's own rule
        (``kv_store.attended_slots``), so the host's count is what each
        program read: every live row's own pages where the step reads
        the arena in place, else K x L, the rungs of the store's two
        ladders; of B x ``max_seq_len``."""
        cfg = (self.row_model if chunk else self.model).cfg
        b = 1 if chunk else self.n_slots
        leaves = () if chunk else self.page_leaves
        read = [attended_slots(cfg, leaves, b, lens, width) for lens in calls]
        return sum(read), len(read) * b * int(cfg.max_seq_len)

    def window_keys(self, calls: int, t: int = 1) -> Tuple[int, int]:
        """(ring slots read, key slots of the whole row) a row, summed
        over the window layers and ``calls`` cached calls of ``t``
        tokens each (decode steps: 1; a prefill chunk: its width):
        this pool's ``attended_keys``' twin for the layers that keep a ring. (0, 0)
        for a model without one."""
        layers, slots = self.ring_shape
        return (
            calls * layers * ring_keys(slots, t),
            calls * layers * int(self.model.cfg.max_seq_len),
        )

    # ---- host-side page bookkeeping -------------------------------

    @property
    def per_row(self) -> int:
        return self.cache_len // self.page

    def n_pages_for(self, need: int) -> int:
        """Pages covering ``need`` logical slots (= prompt_len +
        max_new - 1: a live row's cursor never passes its budget)."""
        return -(-need // self.page)

    def _match_prefix(self, prompt: Sequence[int]) -> List[int]:
        """Resident pages of the trie that ``prompt`` may attach: its
        match, capped so >= 1 suffix token always remains (the first
        output token's logits need a real forward pass). None of them
        where the pool has no trie (``prefix_decline`` says why)."""
        p = len(prompt)
        if p <= 1 or self.prefix is None:
            return []
        return self.prefix.match(prompt)[: (p - 1) // self.page]

    def acquire_pages(
        self, prompt: Sequence[int], need: int
    ) -> Optional[Tuple[List[int], int]]:
        """Reserve pages for a row: match the prompt against the prefix
        trie, then allocate the rest — evicting refcount-0 trie leaves
        under pressure. Returns (page_ids, shared_n) with row refs
        taken on every id, or None if the arena can't fit the row right
        now (the scheduler treats that like a closed KV budget and
        retries after the next retire)."""
        p = len(prompt)
        n_total = self.n_pages_for(need)
        shared = self._match_prefix(prompt)
        # resource: acquires pages
        # Reference the shared pages FIRST so eviction below can't free
        # them out from under us (match() alone leaves refcount at 0
        # for pages only the trie holds).
        self.allocator.ref(shared)  # resource: acquires pages
        try:
            # Where the resident match ends, the spill tier may still
            # know the next chunks — restore them before prefilling.
            self._extend_shared_from_spill(
                prompt, shared, (p - 1) // self.page
            )
            n_new = n_total - len(shared)
            ids = self.allocator.alloc(n_new)
            if ids is None and self.prefix is not None:
                self.prefix.evict(
                    n_new - self.allocator.n_free, self.allocator,
                    on_evict=self._spill_hook(),
                )
                ids = self.allocator.alloc(n_new)
        except BaseException:
            # Trie surgery raising mid-evict must not strand the
            # shared-page refs taken above (TPU019). ``shared`` was
            # extended in place, so restored pages release too (their
            # trie hold keeps them resident — work not lost).
            self.allocator.release(shared)
            raise
        if ids is None:
            self.allocator.release(shared)
            return None
        if self.prefix is not None and p > 1:
            if shared:
                self.prefix_hits += 1
            else:
                self.prefix_misses += 1
        return shared + ids, len(shared)

    def release_pages(self, ids: Sequence[int]) -> int:
        # resource: releases pages
        return self.allocator.release(ids)

    def register_prefix(
        self, prompt: Sequence[int], page_ids: Sequence[int]
    ) -> None:
        """Adopt the row's FULL prompt pages into the trie (partial
        trailing page and decode pages stay private — they're the
        copy-on-write divergence zone)."""
        if self.prefix is None:
            return
        n_full = len(prompt) // self.page
        adopted = self.prefix.insert(prompt, list(page_ids)[:n_full])
        self.allocator.hold(adopted)

    # ---- spill tier (KV fabric) -----------------------------------

    def _spill_hook(self):
        """``on_evict`` callback for ``PrefixCache.evict``: export each
        victim page's bytes to the spill tier while the arena content
        is still valid. Best-effort — a failed spill degrades to the
        plain eviction this always was, never breaks an admission."""
        if self.trie_spill is None:
            return None

        def cb(path_tokens, page_id):
            try:
                state = self.export_pages_state([page_id])
                # wire: produces kv-spill-page via callback
                self.trie_spill(tuple(path_tokens), state)
                self.spill_pages_out += 1
            except Exception:
                pass

        return cb

    def export_pages_state(self, ids: Sequence[int]) -> Dict[str, Any]:
        """Snapshot arbitrary arena pages (no slot attached) as a
        migration-shaped state dict — the trie-spill serialization.
        Cursors are zeroed placeholders so ``tpufw.serve.bundle``'s
        required header fields are satisfied; ``import_pages`` ignores
        them. Same raw gather as ``export_slot``, so int8 codes +
        scales ship as stored and a later import is bit-identical."""
        ids = [int(i) for i in ids]
        paths, names, leaves, _ = self._pool_flat()
        arrays = _export_pages_jit(
            tuple(leaves),
            jnp.asarray(np.asarray(ids, np.int32)),
            names=names,
        )
        return {
            "page": self.page,
            "kv_quant": self.model.cfg.kv_quant or "",
            "n_pages": len(ids),
            "paths": self.exported_paths(),
            "arrays": [np.asarray(a) for a in arrays],
            "token": 0, "pos": 0, "remaining": 0, "done": True,
            "cache_index": 0, "seen": None,
        }

    def import_pages(
        self, page_ids: Sequence[int], state: Dict[str, Any]
    ) -> None:
        """Scatter a spill bundle's page payload into freshly
        allocated arena pages — the restore half of the spill tier and
        the same layout contract as ``splice_slot`` (page size, quant
        mode, leaf paths all validated before anything touches the
        arena). No cursors, no table row: the pages re-enter service
        through the prefix trie, not a slot."""
        # resource: transfers pages
        if int(state["page"]) != self.page:
            raise ValueError(
                f"spill page size {state['page']} != pool page "
                f"{self.page}"
            )
        if (state.get("kv_quant") or "") != (
            self.model.cfg.kv_quant or ""
        ):
            raise ValueError(
                f"spill kv_quant {state.get('kv_quant')!r} != pool "
                f"kv_quant {self.model.cfg.kv_quant!r}"
            )
        if len(page_ids) != int(state["n_pages"]):
            raise ValueError(
                f"spill bundle carries {state['n_pages']} pages but "
                f"{len(page_ids)} were allocated"
            )
        paths, names, leaves, treedef = self._pool_flat()
        want = self.exported_paths()
        if list(state["paths"]) != want:
            raise ValueError(
                "spill bundle leaf layout does not match this pool "
                f"(got {list(state['paths'])!r}, want {want!r})"
            )
        out = _import_pages_jit(
            tuple(leaves),
            tuple(jnp.asarray(a) for a in state["arrays"]),
            jnp.asarray(np.asarray(page_ids, np.int32)),
            names=names,
        )
        self.cache = jax.tree_util.tree_unflatten(treedef, list(out))

    def _extend_shared_from_spill(
        self, prompt: Sequence[int], shared: List[int], cap: int
    ) -> None:
        """Extend a trie match chunk-by-chunk from the spill tier:
        while the NEXT full-page chunk of ``prompt`` has a spill entry,
        allocate one fresh page (its alloc ref IS the row's reference,
        matching ``ref(shared)`` on matched pages), scatter the bytes
        back in, and re-adopt the path into the trie (held) so later
        requests hit it resident. Mutates ``shared`` in place.

        Best-effort and non-raising: under arena pressure (alloc
        fails) it stops rather than evicting — restoring by evicting
        would just churn pages through the spill tier — and a torn or
        mismatched entry stops the walk; the row prefills the rest."""
        if self.trie_restore is None or self.prefix is None:
            return
        while len(shared) < cap:
            end = (len(shared) + 1) * self.page
            try:
                # wire: consumes kv-spill-page via callback
                state = self.trie_restore(
                    tuple(int(t) for t in prompt[:end])
                )
            except Exception:
                return
            if state is None:
                return
            ids = self.allocator.alloc(1)  # resource: acquires pages
            if ids is None:
                return
            try:
                self.import_pages(ids, state)
            except Exception:
                self.allocator.release(ids)  # resource: releases pages
                return
            adopted = self.prefix.insert(prompt[:end], shared + ids)
            self.allocator.hold(adopted)
            shared.extend(ids)
            self.spill_pages_in += 1

    # ---- device ops -----------------------------------------------

    def _pool_flat(self):
        paths, names, leaves, treedef = _flatten_with_names(self.cache)
        return paths, names, leaves, treedef

    def _aligned_row(self, paths, row_cache):
        row_paths, _, row_leaves, _ = _flatten_with_names(row_cache)
        row_map = dict(zip(row_paths, row_leaves))
        return [row_map.get(p) for p in paths]

    @staticmethod
    def _scale_src(paths, names) -> Tuple[int, ...]:
        """scale-leaf index -> its KV leaf's index (same path, the
        name its role says it scales); -1 elsewhere."""
        by_path = {p: i for i, p in enumerate(paths)}
        src = []
        for p, name in zip(paths, names):
            r = role(name)
            src.append(
                by_path[p.replace(name, r.of)] if r.kind == SCALE else -1
            )
        return tuple(src)

    def insert_paged(
        self,
        slot: int,
        row_cache,
        first,
        pos0: int,
        budget: int,
        page_ids: Sequence[int],
        shared_n: int,
        row_seen=None,
    ) -> None:
        """Occupy ``slot`` with a prefilled contiguous row scattered
        into ``page_ids`` (row refs already taken by
        ``acquire_pages``); the first ``shared_n`` ids are prefix pages
        attached by reference, never written."""
        paths, names, leaves, treedef = self._pool_flat()
        # resource: transfers pages
        row_leaves = self._aligned_row(paths, row_cache)
        table_row = np.zeros((self.per_row,), np.int32)
        table_row[: len(page_ids)] = page_ids
        quant = self.model.cfg.kv_quant == "int8"
        perf = getattr(self, "perf", None)
        if perf is not None:
            # Cost harvest (tpufw.obs.perf; once per program).
            perf.observe_jit(
                "serve_paged_insert",
                _paged_insert_jit,
                (
                    tuple(leaves), tuple(row_leaves),
                    jnp.asarray(table_row), slot, shared_n * self.page,
                    first, pos0, budget, self.token, self.pos,
                    self.done, self.remaining, self.seen, row_seen,
                ),
                kwargs=dict(
                    names=names, scale_src=self._scale_src(paths, names),
                    page=self.page, quant=quant,
                ),
            )
        leaves, self.token, self.pos, self.done, self.remaining, \
            self.seen = _paged_insert_jit(
                tuple(leaves), tuple(row_leaves), jnp.asarray(table_row),
                slot, shared_n * self.page, first, pos0, budget,
                self.token, self.pos, self.done, self.remaining,
                self.seen, row_seen,
                names=names, scale_src=self._scale_src(paths, names),
                page=self.page, quant=quant,
            )
        self.dispatched("insert")
        self.cache = jax.tree_util.tree_unflatten(treedef, list(leaves))
        self.slot_pages[slot] = list(page_ids)

    def _attach_row(self, shared_ids):
        """Fresh B=1 contiguous row cache with ``shared_ids``' pages
        gathered into its first ``len(shared_ids) * page`` slots
        (cursor set accordingly); plain zeros when nothing is shared.

        Fresh BUFFERS every call: the attach and chunk jits DONATE the
        row leaves (their memory becomes the attached cache), so a
        cached tree would hand already-deleted buffers to the second
        admission. Only the shapes are kept (``_find_row_shapes``); one
        dispatch of ``_fresh_row`` makes the zeros."""
        with self.tracer.span(
            "serve_row_alloc", shared_pages=len(shared_ids),
            state_bytes=self.state_bytes // self.n_slots,
            window_bytes=self.window_bytes // self.n_slots,
        ):
            row_tree = self._fresh_row()
            self.dispatched("row")
            if not len(shared_ids):
                return row_tree
            paths, names, leaves, _ = self._pool_flat()
            row_paths, _, row_leaves, row_treedef = _flatten_with_names(
                row_tree
            )
            row_map = dict(zip(row_paths, row_leaves))
            aligned = [row_map.get(p) for p in paths]
            quant = self.model.cfg.kv_quant == "int8"
            src = self._scale_src(paths, names)
            scale_of = tuple(
                src.index(i) if i in src else -1
                for i in range(len(paths))
            )
            attached = _attach_shared_jit(
                tuple(aligned), tuple(leaves),
                jnp.asarray(np.asarray(shared_ids, np.int32)),
                names=names, scale_of=scale_of, page=self.page,
                quant=quant,
            )
            return jax.tree_util.tree_unflatten(
                row_treedef, [a for a in attached if a is not None]
            )

    def prefill_shared(self, prompt: Sequence[int], shared_ids, rng):
        """Prefix-hit admission: attach ``shared_ids``' pages to a
        fresh row cache, prefill only the suffix. Same return contract
        as ``tpufw.infer.slots.prefill_row`` — (row_cache, first_arr,
        first_int, done0, seen)."""
        row_cache = self._attach_row(shared_ids)
        length = len(shared_ids) * self.page
        suffix = jnp.asarray(
            np.asarray(prompt[length:], np.int32)[None, :]
        )
        full = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
        cache, first, done, seen = _suffix_prefill_jit(
            self.row_model, self.params, row_cache, suffix, full,
            length, rng, sampling=self.sampling, eos_id=self.eos_id,
        )
        return cache, first, int(np.asarray(first)[0]), done, seen

    # ---- chunked prefill ------------------------------------------

    def start_chunked(
        self, prompt: Sequence[int], need: int, rng,
        chunk_pages: int,
    ) -> ChunkedPrefill:
        """Open a chunked prefill: match the prompt against the prefix
        trie (a checkpoint from a preempted admission resumes here for
        free), reference whatever is shared, and return the cursor
        object ``chunk_step`` advances. Acquires NO new pages — every
        page grab happens page-aligned inside ``chunk_step`` — and
        reads NO pool leaves: the shared-prefix attach (the one
        admission-time device read) is deferred into the first
        ``chunk_step``, whose caller already guarantees leaf
        exclusivity, so an engine may admit mid-chunk even while a
        donated chunk jit is in flight. ``need`` is
        the slot count the FINISHED row must own pages for (prompt +
        decode budget for an in-place admission; just the prompt for a
        prefill engine exporting prompt-only bundles)."""
        prompt = [int(t) for t in prompt]
        p = len(prompt)
        shared = self._match_prefix(prompt)
        # resource: acquires pages
        # ref() pins the shared pages host-side right now (eviction
        # can't reclaim them); their KV is gathered lazily by the
        # first chunk_step. refcounts make the deferral safe: pinned
        # pages are never reallocated, so their content is stable.
        self.allocator.ref(shared)  # resource: acquires pages
        try:
            # Spill-tier continuation of the resident match, same as
            # acquire_pages (restored pages join the deferred attach).
            self._extend_shared_from_spill(
                prompt, shared, (p - 1) // self.page
            )
            if self.prefix is not None and p > 1:
                if shared:
                    self.prefix_hits += 1
                else:
                    self.prefix_misses += 1
            seen = None
            if _track_seen(self.sampling):
                m = np.zeros((1, self.model.cfg.vocab_size), bool)
                if shared:
                    m[0, np.asarray(
                        prompt[: len(shared) * self.page], np.int64
                    )] = True
                seen = jnp.asarray(m)
            cp = ChunkedPrefill(
                prompt=prompt,
                rng=rng,
                chunk_pages=max(1, int(chunk_pages)),
                n_total=self.n_pages_for(max(need, p)),
                row_cache=None,  # first chunk_step attaches (leaf read)
                seen_row=seen,
                cursor=len(shared) * self.page,
                page_ids=list(shared),
                shared_n=len(shared),
            )
        except BaseException:
            # A host-array failure here must not strand the shared
            # refs: nobody has the cursor object yet (TPU019).
            self.allocator.release(shared)
            raise
        return cp

    def chunk_extent(self, cp: ChunkedPrefill) -> Tuple[int, int, bool]:
        """(padded width, real tokens, is it the final chunk) of the
        chunk ``chunk_step`` would run next for ``cp``."""
        left = len(cp.prompt) - cp.cursor
        width = min(cp.chunk_pages, -(-left // self.page)) * self.page
        return width, min(left, width), left <= width

    def chunk_step(
        self, cp: ChunkedPrefill, unlocked=None
    ) -> str:
        """Advance ``cp`` by one page-aligned chunk. Returns "ran"
        (progress, more chunks to go), "done" (first token sampled,
        ready for ``finalize_chunked``), or "stalled" (the arena could
        not supply this chunk's pages right now — safe to retry after
        the next release; nothing was consumed).

        Completed full pages are checkpointed into the prefix trie
        after EVERY chunk, so an abandon at any point leaves a resume
        point behind — and concurrent identical prompts start sharing
        pages before this prefill even finishes.

        ``unlocked``, if given, is a context-manager FACTORY that
        releases the caller's pool mutex around the pure-compute jit
        call: every shared-state mutation (allocator, trie, pool
        leaves) happens outside it, so admissions and abandons can
        interleave with a chunk's device time — but the CALLER must
        still guarantee only one chunk_step is in flight per pool
        (concurrent calls would fork the arena leaves)."""
        # No acquires-contract here: every page this call grabs is
        # transferred into cp.page_ids before it can return or raise,
        # so the CALLER holds nothing — cp's owner discharges via
        # finalize_chunked / abandon_chunked.
        start = cp.cursor
        width, n_real, is_final = self.chunk_extent(cp)
        # The final chunk acquires the full remaining page need —
        # including the decode-budget tail — BEFORE compute, so a
        # finished prefill can always finalize.
        target = cp.n_total if is_final else (start + width) // self.page
        n_new = target - len(cp.page_ids)
        if n_new > 0:
            ids = self.allocator.alloc(n_new)
            if ids is None and self.prefix is not None:
                self.prefix.evict(
                    n_new - self.allocator.n_free, self.allocator,
                    on_evict=self._spill_hook(),
                )
                ids = self.allocator.alloc(n_new)
            if ids is None:
                return "stalled"
            cp.page_ids.extend(ids)  # resource: transfers pages
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :n_real] = np.asarray(
            cp.prompt[start:start + n_real], np.int32
        )
        first_pg = start // self.page
        chunk_ids = np.asarray(
            cp.page_ids[first_pg:first_pg + width // self.page],
            np.int32,
        )
        paths, names, leaves, treedef = self._pool_flat()
        quant = self.model.cfg.kv_quant == "int8"
        with (unlocked() if unlocked is not None
              else contextlib.nullcontext()):
            if cp.row_cache is None:
                # Deferred shared-prefix attach: the one pool-leaf
                # read of a chunked admission, pulled out of
                # start_chunked and into this busy window so
                # admissions never race a donated in-flight chunk.
                # Safe here — the single-flight contract means no
                # other chunk can donate these leaves mid-read.
                cp.row_cache = self._attach_row(
                    cp.page_ids[: cp.shared_n]
                )
            out_leaves, cp.row_cache, first, done0, cp.seen_row = (  # resource: donates leaves
                _prefill_chunk_jit(
                    tuple(leaves), cp.row_cache, self.params,
                    jnp.asarray(tokens), jnp.asarray(chunk_ids),
                    np.int32(start), np.int32(n_real),
                    np.bool_(is_final), cp.rng, cp.seen_row,
                    row_model=self.row_model, sampling=self.sampling,
                    eos_id=self.eos_id, paths=paths, names=names,
                    scale_src=self._scale_src(paths, names),
                    page=self.page, quant=quant,
                )
            )
            self.dispatched("chunk")
            if unlocked is not None:
                # Dispatch is async — pin the device wall inside the
                # lock-released window, not under some later holder.
                jax.block_until_ready(
                    (out_leaves, cp.row_cache, first, done0)
                )
        self.cache = jax.tree_util.tree_unflatten(
            treedef, list(out_leaves)
        )
        cp.cursor = start + n_real
        cp.n_chunks += 1
        if self.prefix is not None:
            # Per-chunk trie checkpoint: the committed prefix's full
            # pages become shareable (and survive an abandon).
            n_full = cp.cursor // self.page
            adopted = self.prefix.insert(
                cp.prompt[:cp.cursor], cp.page_ids[:n_full]
            )
            self.allocator.hold(adopted)
        if is_final:
            cp.first = first
            # The one read of a chunked prefill that blocks: it waits
            # for every program queued before it, this chunk's last,
            # until both results are ready (``serve_device_wait``: the
            # device running), then copies them to the host
            # (``serve_fetch``: two reads of one program's results).
            with self.tracer.span(
                "serve_device_wait", **{"for": "prefill_final"}
            ):
                first.copy_to_host_async()  # as np.asarray did: queued
                done0.copy_to_host_async()  # behind the program
                jax.block_until_ready((first, done0))
            with self.tracer.span("serve_fetch"):
                cp.first_int = int(np.asarray(first)[0])
                cp.done0 = bool(np.asarray(done0)[0])
            return "done"
        return "ran"

    def finalize_chunked(
        self, slot: int, cp: ChunkedPrefill, budget: int
    ) -> None:
        """Occupy ``slot`` with a completed chunked prefill. The arena
        already holds every prompt page (chunk_step scattered them), so
        ``insert_paged`` is reused with ``shared_n = per_row``: its
        window scatter redirects entirely into reserved page 0 and the
        call just installs the table row + cursors — zero new program
        keys. The row cache's cache_index (fixed to the prompt length
        inside the chunk jit) supplies the slot cursor."""
        # resource: transfers pages
        self.insert_paged(
            slot, cp.row_cache, cp.first_int, len(cp.prompt), budget,
            cp.page_ids, self.per_row, row_seen=cp.seen_row,
        )

    def abandon_chunked(self, cp: ChunkedPrefill) -> int:
        """Preempt/fail path: drop the row's page references. Trie-
        checkpointed full pages stay resident (held) — that IS the
        resume point a re-admission's ``start_chunked`` picks up —
        while unheld pages free immediately. Returns pages freed."""
        # resource: releases pages
        freed = self.allocator.release(cp.page_ids)
        cp.page_ids = []
        return freed

    def release_slot(self, slot: int) -> int:
        """Free ``slot``: freeze its masks, zero its page-table row,
        return its pages to the allocator. Returns pages actually freed
        (shared/held pages may stay resident). Per-slot state is left
        as it lies: a freed slot's is read by nobody, and the next
        ``insert_paged`` overwrites all of it."""
        # resource: releases pages
        # resource: releases slot
        self.done, self.remaining = _retire_jit(
            self.done, self.remaining, slot
        )
        paths, names, leaves, treedef = self._pool_flat()
        t_idx = [
            i for i, n in enumerate(names) if role(n).kind == TABLE
        ]
        cleared = _clear_tables_jit(
            tuple(leaves[i] for i in t_idx), slot
        )
        for i, t in zip(t_idx, cleared):
            leaves[i] = t
        self.cache = jax.tree_util.tree_unflatten(treedef, leaves)
        freed = self.allocator.release(self.slot_pages[slot])
        self.slot_pages[slot] = []
        return freed

    # ---- page migration (disaggregated serving) -------------------

    def exported_paths(self) -> List[str]:
        """Leaf paths that travel in a page bundle, in pool-flat order
        — the layout contract both ends of a migration must agree on."""
        paths, names, _, _ = self._pool_flat()
        return [p for p, n in zip(paths, names) if role(n).in_arena]

    def export_slot(
        self, slot: int, page_ids: Optional[Sequence[int]] = None
    ) -> Dict[str, Any]:
        """Snapshot slot ``slot``'s KV pages + cursors as a host-side
        migration state dict (tpufw.serve.bundle serializes it).

        MUST run before ``release_slot``: after release the device
        table row is zeroed (reads would gather reserved page 0's
        junk) and the pages may already belong to a new admission.
        ``page_ids`` lets the caller pass the page-table snapshot it
        took at the chunk boundary — the scheduler's retire path does,
        so a row finishing mid-chunk exports the pages it owned when
        the chunk was launched, not whatever the list mutated to."""
        # resource: transfers slot
        reject_state(self, "export_slot")
        ids = list(
            self.slot_pages[slot] if page_ids is None else page_ids
        )
        paths, names, leaves, _ = self._pool_flat()
        arrays = _export_pages_jit(
            tuple(leaves),
            jnp.asarray(np.asarray(ids, np.int32)),
            names=names,
        )
        cache_index = 0
        for n, leaf in zip(names, leaves):
            if role(n).kind == CURSOR:
                # Every layer carries the same per-slot value.
                cache_index = int(
                    np.asarray(leaf).reshape(-1, self.n_slots)[0, slot]
                )
                break
        seen_row = None
        if self.seen is not None:
            seen_row = np.asarray(self.seen[slot])
        return {
            "page": self.page,
            "kv_quant": self.model.cfg.kv_quant or "",
            "n_pages": len(ids),
            "paths": self.exported_paths(),
            "arrays": [np.asarray(a) for a in arrays],
            "token": int(np.asarray(self.token)[slot]),
            "pos": int(np.asarray(self.pos)[slot]),
            "remaining": int(np.asarray(self.remaining)[slot]),
            "done": bool(np.asarray(self.done)[slot]),
            "cache_index": cache_index,
            "seen": seen_row,
        }

    def splice_slot(
        self, slot: int, state: Dict[str, Any],
        page_ids: Sequence[int],
    ) -> None:
        """Occupy ``slot`` with a migrated bundle: scatter its page
        payload into ``page_ids`` (already allocated, row refs taken)
        and restore the cursors. Raises ValueError on any layout
        mismatch — a bundle from a differently-shaped pool must be
        rejected before it scribbles on the arena."""
        # resource: transfers pages
        reject_state(self, "splice_slot")
        if int(state["page"]) != self.page:
            raise ValueError(
                f"bundle page size {state['page']} != pool page "
                f"{self.page}"
            )
        if (state.get("kv_quant") or "") != (
            self.model.cfg.kv_quant or ""
        ):
            raise ValueError(
                f"bundle kv_quant {state.get('kv_quant')!r} != pool "
                f"kv_quant {self.model.cfg.kv_quant!r}"
            )
        if len(page_ids) < int(state["n_pages"]):
            raise ValueError(
                f"bundle carries {state['n_pages']} pages but only "
                f"{len(page_ids)} were allocated"
            )
        paths, names, leaves, treedef = self._pool_flat()
        want = self.exported_paths()
        if list(state["paths"]) != want:
            raise ValueError(
                "bundle leaf layout does not match this pool "
                f"(got {list(state['paths'])!r}, want {want!r}) — "
                "model config / cache structure drift between replicas"
            )
        seen_row = state.get("seen")
        if (seen_row is None) != (self.seen is None):
            raise ValueError(
                "bundle and pool disagree on repetition-penalty "
                "tracking (seen mask present on one side only)"
            )
        # The table row maps EVERY allocated page (a prompt-only bundle
        # from a chunked prefill ships fewer pages than the row's full
        # prompt+budget need — the extra tail pages hold junk until
        # decode's append writes them, and slots past the cursor are
        # causally masked until then); the payload scatter only touches
        # the pages the bundle actually carries.
        table_row = np.zeros((self.per_row,), np.int32)
        table_row[: len(page_ids)] = page_ids
        leaves_out, self.token, self.pos, self.done, self.remaining, \
            self.seen = _splice_pages_jit(
                tuple(leaves),
                tuple(jnp.asarray(a) for a in state["arrays"]),
                jnp.asarray(np.asarray(
                    page_ids[: int(state["n_pages"])], np.int32
                )),
                jnp.asarray(table_row),
                slot,
                np.int32(state["cache_index"]),
                np.int32(state["token"]),
                np.int32(state["pos"]),
                np.int32(state["remaining"]),
                np.bool_(state["done"]),
                self.token, self.pos, self.done, self.remaining,
                self.seen,
                None if seen_row is None else jnp.asarray(seen_row),
                names=names,
            )
        self.cache = jax.tree_util.tree_unflatten(
            treedef, list(leaves_out)
        )
        self.slot_pages[slot] = list(page_ids)

    def retire(self, slot: int) -> None:
        """Error-path retire — page-aware (frees the row's pages)."""
        self.release_slot(slot)

    def insert(self, *a, **k):  # pragma: no cover - guard rail
        raise TypeError(
            "PagedSlotPool: use insert_paged (pages must be acquired "
            "through the allocator first)"
        )
