"""The KV store: how a decode cache is laid out, written and read.

A model says what it stores per token (``append``: llama's K/V heads,
DeepSeek's MLA latents) or per row (``slot_state``: a linear-attention
layer's recurrent state) and how it attends; this module owns the
leaves of the flax "cache" collection, their names and their roles.
``tpufw.infer`` moves rows in and out of pools by asking ``role()``
what a leaf is, never by its name: a family that stores a new kind of
page adds one row to ``_LEAVES`` and nothing to ``infer/``.

Layouts (chosen from what the code can observe: ``cfg.kv_page``, the
cursor's rank, ``cfg.kv_quant``):

- CONTIGUOUS, ``[B, max_seq_len, *feat]`` rows. Static shapes: masking
  does the rest. Never-written slots keep segment 0, so the segment
  mask hides them (prompt pad slots stay 0 too). A scalar cursor
  (``generate``, and every B=1 prefill: the row twin of a chunked
  prefill is this store) appends with ``dynamic_update_slice``; a
  ``[B]`` cursor (tpufw.infer.slots pool decode) scatters each row at
  its own offset. The write window is clamped so a retired-but-still-
  stepped row scatters in bounds: its output is masked host-side and
  the clamped slots are overwritten by the next insert's whole-row
  copy. This store is the tests' reference for the paged one.
- PAGED (``cfg.kv_page > 0``), a global arena of ``kv_pages`` pages x
  ``kv_page`` slots shared by every row; ``page_table`` [B, S/page]
  maps a row's logical slot j to physical page table[j // page], offset
  j % page. The view gathers the logical [B, S] row IN LOGICAL SLOT
  ORDER, so attention sees exactly what the contiguous store shows at
  every written slot and the output is bit-equal at matching
  precision. Unmapped table entries point at reserved page 0, the junk
  sink: its contents only ever surface at logical slots strictly
  beyond the row's cursor, where the causal mask fills the logit
  before softmax (exp underflows to exact 0.0, and 0.0 * finite junk
  == 0.0). The same clamp keeps a done row's writes either in its own
  private last page (the allocator never shares a row's final page;
  speculative callers keep t <= page so the clamped window never leaves
  it) or, once retired (table zeroed), in page 0 — never a neighbour's.
  Occupancy, table churn and cursor motion are all DATA: one jitted
  program forever. The cursor is ``[B]`` from birth. Prefill still runs
  through a contiguous row and is scattered into pages at insert
  (tpufw.infer.pages).
- INT8 (``cfg.kv_quant == "int8"``, paged only): arenas hold int8
  codes, quantized per token at append, with float32 scales stored
  page-structured ``[kv_pages, kv_page]`` and applied at the view.
- RING (``ring_append``: a layer that attends a window of W keys),
  ``[B, W, *feat]`` rows whatever ``max_seq_len`` and ``kv_page`` are:
  ring slot ``j % W`` holds the row's token at logical slot j, until
  the token at j + W overwrites it. Keys are stored already rotated,
  so the ring is never read in order: each ring slot carries its
  logical slot (``ring_slot``) and segment id (``ring_segment``; 0 =
  never written), and the mask is over those (query at logical slot q
  may attend a ring slot iff ``0 <= q - its slot < W`` and the
  segments match). Tokens with segment id 0 (prompt padding, a pool's
  done rows) are NOT written: the ring is the last W real tokens. A
  call of one token writes, then reads the W ring slots; a call of
  t > 1 tokens reads the ring as it stood beside its own t tokens
  (W + t keys, one static length), then keeps the last W of them. No
  ladder and no switch: the view never grows with the row. In the
  pools a ring is per-slot like STATE: written whole at insert, so a
  reused slot holds nothing of the row before it; no page bundle
  carries it, a prefix of pages does not determine it and a verify
  block cannot un-write it (``Role.per_slot``).

THE LIVE PREFIX. A cached call reads the first L slots of the row, not
all ``max_seq_len``: L is the rows' live length rounded up to a rung of
``key_ladder`` (static lengths: S/8, S/4, S/2, S, none under 2048 slots,
each a whole number of pages), and the rung is chosen INSIDE the program
by a ``lax.switch`` on a scalar it computes from the cursors, so no
program gains a key and no pool an argument. The live length is ``cur +
t`` under the scalar cursor and, under ``[B]`` cursors, the largest
``cursor + t`` over the rows whose tokens of this call carry a segment
id > 0: a pool's done rows keep stepping and their cursors keep
counting, so the pools step them with segment 0 (tpufw.infer.slots).
The page table cannot bound it: a row is granted its whole budget up
front. Slots past L are exactly those the causal mask fills with -1e30,
whose weights underflow to an exact 0.0: the same mathematics at the
same precision. A ladder of one rung (``max_seq_len`` < 4096) is the
program without a switch. ``attended_keys`` is the same rule for the
host, which counts what the device read (tpufw.workloads.serve).

t == 1 is the plain decode step; t > 1 is a prefill chunk (contiguous)
or the speculative verify block (tpufw.infer.speculative): all t tokens
land in consecutive logical slots first, then the view includes them,
so intra-block causality falls out of the same slot-ordered mask.
Causality is over cache SLOTS (``q_slots``), not RoPE positions: under
left-padding a token's RoPE position lags its slot by the pad length
and would wrongly mask valid recent slots.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from tpufw.ops.quant import dequantize_kv, quantize_kv

PAGE, SCALE, SEGMENT, TABLE, CURSOR, STATE, RING = (
    "page", "scale", "segment", "table", "cursor", "state", "ring"
)


class Role(NamedTuple):
    """What a cache leaf is. ``rank`` is its unstacked rank in a paged
    pool (nn.scan stacks layer axes in front): ``(n_pages, page, *feat)``
    for what lives in the arena — the trailing ``rank - 2`` dims of a
    PAGE are the per-token feature block one int8 scale covers, and the
    matching row leaf is ``(1, W, *feat)`` at the same rank —
    ``(B, *feat)`` for per-slot STATE and RING. ``of`` names the PAGE
    leaf a SCALE belongs to."""

    kind: str
    rank: int
    of: str = ""

    @property
    def in_arena(self) -> bool:
        """Indexed by physical page: what a page bundle carries."""
        return self.kind in (PAGE, SCALE, SEGMENT)

    @property
    def per_slot(self) -> bool:
        """``[B, *feat]``, a slot's own and whole at every insert: what
        no page bundle carries and no prefix of pages determines."""
        return self.kind in (STATE, RING)


_SEGMENT, _TABLE, _CURSOR = "cached_segment_ids", "page_table", "cache_index"
_SCALE = "_scale"
_LEAVES: Dict[str, Role] = {
    "cached_key": Role(PAGE, 4), "cached_value": Role(PAGE, 4),  # K/V heads
    "cached_ckv": Role(PAGE, 3), "cached_kpe": Role(PAGE, 3),  # MLA latents
    _SEGMENT: Role(SEGMENT, 2),
    _TABLE: Role(TABLE, 2),
    _CURSOR: Role(CURSOR, 1),
    # Per-slot STATE: what a row keeps between steps that is neither a
    # page nor a table or cursor (tpufw.models.solar_open2.KDALayer: the
    # recurrent state and its convolution's tail). A prefix of pages
    # does not determine it, a verify block cannot rewind it and a page
    # bundle does not carry it, so the prefix trie, speculation and slot
    # export decline a model that has any (tpufw.infer.slots
    # ``reject_state``).
    "kda_state": Role(STATE, 4), "conv_state": Role(STATE, 3),
    # A state-space mixer's [B, H, P, N] state (tpufw.models.falcon_h1
    # SSMMixer, beside the same ``conv_state``): there EVERY layer holds
    # it beside a page pair.
    "ssm_state": Role(STATE, 4),
    # A window layer's RING (``ring_append``): the last ``window`` keys
    # and values of each row, the logical slot and the segment id of
    # each ring slot. Per-slot like STATE, and declined where STATE is.
    "ring_key": Role(RING, 4), "ring_value": Role(RING, 4),
    "ring_slot": Role(RING, 2), "ring_segment": Role(RING, 2),
}
STATE_LEAVES = {n: r.rank for n, r in _LEAVES.items() if r.kind == STATE}


class Decline(NamedTuple):
    """What a pool that holds per-slot leaves of a kind declines prefix
    sharing, slot export and speculation with: the scheduler's label,
    what it keeps and what a continuation without it would start from
    (the refusal's words)."""

    reason: str
    keeps: str
    wrong: str


#: The one rule for "this model has leaves a page bundle does not
#: carry", by ``Role.kind`` (tpufw.infer.slots ``per_slot_decline``).
DECLINES: Dict[str, Decline] = {
    STATE: Decline(
        "state_layers",
        f"keeps per-slot state ({', '.join(STATE_LEAVES)}) beside its "
        "pages; snapshots of state are not built yet",
        "state",
    ),
    RING: Decline(
        "window_layers",
        "keeps a ring of its window layers' last keys per slot beside "
        "its pages; a page bundle does not carry a ring, pages do not "
        "determine it and a verify block cannot un-write it",
        "keys",
    ),
}


def role(name: str) -> Role:
    """The role of cache leaf ``name``. An unknown leaf raises: every
    program that moves rows must know every leaf's role (an untouched
    leaf would leak the previous occupant's state)."""
    known = _LEAVES.get(name)
    if known is not None:
        return known
    of = name[: -len(_SCALE)] if name.endswith(_SCALE) else ""
    if of in _LEAVES and _LEAVES[of].kind == PAGE:
        return Role(SCALE, 2, of)
    raise ValueError(
        f"unknown cache leaf {name!r}: tpufw.ops.kv_store must know "
        "every leaf's role"
    )


def leaf_name(path) -> str:
    """The name of the leaf at pytree ``path`` of a cache collection."""
    last = path[-1]
    return str(getattr(last, "key", last))


def path_role(path) -> Role:
    return role(leaf_name(path))


def _declare(module, name, shape, dtype):
    return module.variable("cache", name, jnp.zeros, shape, dtype)


def slot_state(module, name: str, shape: Tuple[int, ...], dtype):
    """Declare per-slot state ``name`` [B, *feat] on ``module`` (zero is
    a row's empty past). Its update rule is the model's own."""
    known = role(name)
    if known.kind != STATE or known.rank != len(shape):
        raise ValueError(f"{name!r} {shape} is not declared as {known}")
    return _declare(module, name, shape, dtype)


#: No rung of the ladder is shorter. Each rung is one more copy of every
#: attention layer in every cached program, and a warm start pays for it
#: in executables to load (measured, PR 31: 3 s a rung for eight latent
#: layers), while a 512-token chunk spends 13 us a key slot beside 34 ms
#: that no rung shortens: under 2048 slots a rung saves less than it costs.
MIN_RUNG = 2048


def key_ladder(max_seq_len: int, page: int = 0) -> Tuple[int, ...]:
    """The key lengths a cached call may read, ascending, the whole row
    last: S/8, S/4, S/2 where they divide S, hold ``MIN_RUNG`` slots and
    are whole pages. A function of the row and the page alone."""
    s, unit = int(max_seq_len), max(int(page), 1)
    return tuple(
        s >> k for k in (3, 2, 1)
        if s % (1 << k) == 0 and s >> k >= MIN_RUNG and (s >> k) % unit == 0
    ) + (s,)


def key_rung(ladder: Tuple[int, ...], live):
    """Index of the shortest rung that holds ``live`` key slots; the
    top rung for anything longer. ``live`` is a Python int (the host) or
    a traced scalar (the program): one rule for both."""
    return sum((live > rung) * 1 for rung in ladder[:-1])


def attended_keys(cfg, live: int) -> int:
    """Key slots of each row a cached call of ``cfg``'s model reads when
    its longest live row holds ``live`` slots, this call's tokens
    included: what the program's switch picks, for the host's count."""
    ladder = key_ladder(cfg.max_seq_len, getattr(cfg, "kv_page", 0))
    return ladder[key_rung(ladder, int(live))]


def _head(x: jax.Array, n: int) -> jax.Array:
    """``x[:, :n]``; ``x`` itself where that is all of it."""
    return x if n == x.shape[1] else x[:, :n]


def append(module, cfg, new: Dict[str, jax.Array], segment_ids):
    """Append this call's tokens at the cache cursor; hand back how to
    read the cache under the live-prefix bound.

    Called from inside flax ``module``. ``new`` maps PAGE leaf names to
    ``[B, t, *feat]``; ``segment_ids`` [B, t] (None: all 1) are the
    tokens' own; a row whose ids are all 0 is not live (its slots are
    written all the same). Returns ``(read, segment_ids, q_slots)``: the
    queries' segment ids as stored, the logical slots the t queries sit
    at (``[B, t]``, or ``[1, t]`` under a scalar cursor) and ``read``,
    which runs the caller's contraction over the live prefix:
    ``read(attend)`` is ``attend(views, kv_segment_ids)`` with each
    leaf's first L logical slots ``[B, L, *feat]`` (the new tokens
    included, in ``cfg.dtype``) and the slots' ids ``[B, L]`` (0 = never
    written), L the rung of ``key_ladder`` that holds every live row
    (module docstring). ``attend`` is traced once per rung and must
    return the same shapes at each; it may not touch flax variables.
    Query i may attend slot j iff ``j <= q_slots[., i]`` and the
    segments match: ``attention_mask(t, L, ...)``.
    """
    for name, x in new.items():
        if role(name) != Role(PAGE, x.ndim):
            raise ValueError(f"{name!r} rank {x.ndim} is not {role(name)}")
    b, t = next(iter(new.values())).shape[:2]
    s, page = cfg.max_seq_len, getattr(cfg, "kv_page", 0)
    seg = (
        jnp.ones((b, t), jnp.int32) if segment_ids is None
        else segment_ids.astype(jnp.int32)
    )
    if page:
        if s % page:
            raise ValueError(f"kv_page={page} must divide max_seq_len={s}")
        quant = cfg.kv_quant == "int8"
        lead, dtype = (cfg.kv_pages, page), jnp.int8 if quant else cfg.dtype
    else:
        quant, lead, dtype = False, (b, s), cfg.dtype
    store = {
        n: _declare(module, n, lead + x.shape[2:], dtype)
        for n, x in new.items()
    }
    cseg = _declare(module, _SEGMENT, lead, jnp.int32)
    cursor = _declare(module, _CURSOR, (b,) if page else (), jnp.int32)
    cur = cursor.value
    if cur.ndim == 0:
        at = (0, cur)
        for n, x in new.items():
            store[n].value = jax.lax.dynamic_update_slice(
                store[n].value, x.astype(dtype), at + (0,) * (x.ndim - 2)
            )
        cseg.value = jax.lax.dynamic_update_slice(cseg.value, seg, at)
        q_slots = (cur + jnp.arange(t))[None, :]
    else:
        q_slots = jnp.minimum(cur, s - t)[:, None] + jnp.arange(t)[None, :]
        if page:
            table = _declare(module, _TABLE, (b, s // page), jnp.int32)
            at = (table.value[jnp.arange(b)[:, None], q_slots // page],
                  q_slots % page)
        else:
            at = (jnp.arange(b)[:, None], q_slots)
        if quant:
            scales = {
                n: _declare(module, n + _SCALE, lead, jnp.float32)
                for n in new
            }
            coded = {
                n: quantize_kv(x, n_feat=x.ndim - 2) for n, x in new.items()
            }
            for n in new:
                store[n].value = store[n].value.at[at].set(coded[n][0])
            for n in new:
                scales[n].value = scales[n].value.at[at].set(coded[n][1])
        else:
            for n, x in new.items():
                store[n].value = store[n].value.at[at].set(x.astype(dtype))
        cseg.value = cseg.value.at[at].set(seg)
    cursor.value = cur + t

    # The writes are done; from here the store's VALUES are read, so a
    # branch of the switch below touches no flax variable.
    arenas = {n: v.value for n, v in store.items()}
    ids = cseg.value
    idx = table.value if page else None
    scale_of = {n: v.value for n, v in scales.items()} if quant else {}

    def view(length: int):
        """(views, kv_segment_ids) of the first ``length`` slots."""
        if not page:
            return (
                {n: _head(a, length) for n, a in arenas.items()},
                _head(ids, length),
            )
        rows = _head(idx, length // page)
        views = {}
        for n, x in new.items():
            pages = arenas[n][rows]
            if quant:
                pages = dequantize_kv(pages, scale_of[n][rows], cfg.dtype)
            views[n] = pages.reshape((b, length) + x.shape[2:])
        return views, ids[rows].reshape(b, length)

    ladder = key_ladder(s, page)

    def read(attend: Callable):
        if len(ladder) == 1:
            return attend(*view(s))
        if cur.ndim == 0:
            live = cur + t
        else:
            live = jnp.max(
                jnp.where(jnp.any(seg > 0, axis=1), q_slots[:, -1] + 1, 0)
            )
        return jax.lax.switch(
            key_rung(ladder, live),
            [lambda n=n: attend(*view(n)) for n in ladder],
        )

    return read, seg, q_slots


def ring_keys(window: int, t: int) -> int:
    """Keys a call of ``t`` tokens reads of a window layer's ring, a row:
    the ring after its own write (t == 1), or the ring as it stood beside
    the call's own tokens. The one rule for ``ring_append`` and for the
    host, which counts what the device read (tpufw.workloads.serve)."""
    return int(window) if t == 1 else int(window) + int(t)


def ring_layers(cache) -> Tuple[int, int]:
    """(window layers, ring slots a row) of a cache pytree, by its
    ``ring_slot`` leaves ``[*stack, B, window]``; (0, 0) without rings."""
    layers = slots = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if leaf_name(path) == "ring_slot":
            layers += int(math.prod(leaf.shape[:-2]))
            slots = int(leaf.shape[-1])
    return layers, slots


def ring_append(module, cfg, new: Dict[str, jax.Array], segment_ids, window):
    """Append this call's tokens to a window layer's ring; hand back how
    to read it (module docstring, RING).

    ``new`` maps ``"ring_key"`` / ``"ring_value"`` to ``[B, t, *feat]``.
    Returns ``(read, segment_ids, q_slots)`` as ``append`` does;
    ``read(attend)`` is ``attend(views, kv_segment_ids, kv_slots)`` with
    ``[B, L, *feat]`` views, the keys' segment ids and their logical
    slots ``[B, L]``, L = ``window`` for t == 1 and ``window + t`` for a
    wider call: one static length a program, so ``attend`` is traced
    once. Query i may attend key j iff ``0 <= q_slots[., i] -
    kv_slots[., j] < window`` and the segments match:
    ``attention_mask(..., kv_positions=kv_slots, sliding_window=window)``.
    The cursor is ``append``'s: a scalar (``generate``, a row twin), or
    ``[B]`` in a pool, where it counts a done row's steps too."""
    for name, x in new.items():
        if role(name) != Role(RING, x.ndim):
            raise ValueError(f"{name!r} rank {x.ndim} is not {role(name)}")
    b, t = next(iter(new.values())).shape[:2]
    r = int(window)
    seg = (
        jnp.ones((b, t), jnp.int32) if segment_ids is None
        else segment_ids.astype(jnp.int32)
    )
    store = {
        n: _declare(module, n, (b, r) + x.shape[2:], cfg.dtype)
        for n, x in new.items()
    }
    rslot = _declare(module, "ring_slot", (b, r), jnp.int32)
    rseg = _declare(module, "ring_segment", (b, r), jnp.int32)
    paged = bool(getattr(cfg, "kv_page", 0))
    cursor = _declare(module, _CURSOR, (b,) if paged else (), jnp.int32)
    cur = cursor.value
    q_slots = (cur[:, None] if cur.ndim else cur) + jnp.arange(t)[None, :]
    slots = jnp.broadcast_to(q_slots, (b, t))
    # The ring as it stood: what a call of t > 1 tokens reads.
    old = {n: v.value for n, v in store.items()}
    old_seg, old_slots = rseg.value, rslot.value
    # The last ``r`` REAL tokens of the call are kept; what is not kept
    # scatters out of bounds and is dropped.
    real = seg > 0
    after = jnp.cumsum(real[:, ::-1], axis=1)[:, ::-1] - real
    at = (
        jnp.arange(b)[:, None],
        jnp.where(real & (after < r), slots % r, r),
    )
    for n, x in new.items():
        store[n].value = store[n].value.at[at].set(
            x.astype(cfg.dtype), mode="drop"
        )
    rslot.value = rslot.value.at[at].set(slots, mode="drop")
    rseg.value = rseg.value.at[at].set(seg, mode="drop")
    cursor.value = cur + t
    if ring_keys(r, t) == r:
        views = {n: v.value for n, v in store.items()}
        kv_seg, kv_slots = rseg.value, rslot.value
    else:
        views = {
            n: jnp.concatenate([old[n], x.astype(cfg.dtype)], axis=1)
            for n, x in new.items()
        }
        kv_seg = jnp.concatenate([old_seg, seg], axis=1)
        kv_slots = jnp.concatenate([old_slots, slots], axis=1)

    def read(attend: Callable):
        return attend(views, kv_seg, kv_slots)

    return read, seg, q_slots
