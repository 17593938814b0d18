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

WHO READS HOW. A pool's DECODE STEP over K/V heads reads the arena IN
PLACE (``in_place``: ``[B]`` cursors, one token a row, a paged arena that
is not int8, an ``attend`` that offers a paged form, on the chip): one
Pallas kernel (tpufw.ops.paged_attend) walks each live row's OWN pages
through its table row as far as its own cursor, a row that is not live
reads nothing, and nothing is gathered or copied, so neither ladder
below has a say. Every other cached call reads through the two ladders,
and why each stays there: a scalar cursor (``generate``, every prefill
chunk's row twin) is a contiguous row of many query tokens, where a
static slice is already in place and the chunk programs are the
heaviest to trace; a call of t > 1 tokens under ``[B]`` cursors (the
speculative verify block) wants a kernel with a query-block axis; the
contiguous pool is the paged one's reference in the tests; an int8 arena
wants its scales dequantised in the kernel; the latent cache's absorbed
contraction (tpufw.models.deepseek) is another kernel; a window masked
over the arena is not in this kernel's mask (rings are ``ring_append``'s
and have no ladder); and off the chip the ladder read is what runs, the
kernel's reference, so that the suite does not step every pool through
the Pallas interpreter. The ladders go when those have kernels (D12).

READERS THAT ARE NOT THE WRITER. ``append`` writes the call's tokens
and hands back ``read``; WHO calls ``read`` is the model's business. A
family whose later layers attend an earlier layer's keys and values
(tpufw.models.phi4flash: one full-attention layer writes the model's one
page pair, seven cross-attention layers after it project queries only)
hands that layer's ``read``, with the ``seg`` and ``q_slots`` it came
with, down its trunk, and each reader calls it with an ``attend`` and
queries of its own. A reader declares no cache leaf and writes nothing;
its queries sit at the slots the writer's did, so the mask is the
writer's (a key is seen up to and including the query's own position);
every rule above and below holds a reader as it holds the writer: in
place through the kernel where the writer's call is, through the ladders
elsewhere, one more read of the same arena and no copy of it. Readers
whose ``attend`` compares equal to the writer's share its trace of each
branch. ``page_readers`` is the count for the host's books.

THE LIVE PREFIX (the ladders' calls). A cached call reads the first L
slots of the row, not
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
program without a switch. ``attended_pair`` is the same rule for the
host, which counts what the device read (tpufw.workloads.serve:
``attended_slots``, which books a call read in place at each live row's
own pages).

THE LIVE ROWS (the ladders' calls under ``[B]`` cursors: since the
decode step of a K/V pool reads in place, the latent cache's decode step,
verify blocks, int8 arenas and every pool off the chip). Under ``[B]``
cursors a cached call reads K rows of the
pool, not all B: a row is live in a call iff its tokens carry a segment
id > 0 (the rule the live length is computed from), the rows are ordered
live first, stably, and K is the shortest rung of ``row_ladder`` (B/8
where that is whole, then B: a function of B alone, the key ladder's
twin) that holds the live count. The view gathers the pages
(contiguous: the rows) of the first K rows of that order only, ``read``
hands ``attend`` the same K rows of its per-row operands, and the result
is scattered back to ``[B, t, ...]`` with zeros in the rows not read.
Zeros are safe there: a row that is not read is done or empty, the pools
emit pad for it and the host masks it, and nothing a live row computes
reads another row. Rows inside K beyond the live count are dead rows
attending under segment 0, as every dead row did before. The branch of
the one ``lax.switch`` is a pair (K, L) of ``branch_pairs``: the whole
pool at each key rung, and the eighth of it at the whole row (B/8 x S
key slots, no more than the whole pool reads at its lowest rung; a
branch a pair of rungs is priced beside ``ROW_SHIFTS``). K == B is the
program without a gather of queries or a scatter. A scalar cursor has
ONE row rung: every row of such a call sits at the same cursor and is as
live as the next (``generate``), and the row twin of a chunked prefill
is one row, so the many prefill programs gain no branch. So has a pool
over rows of one key rung (``pool_ladders``): its call keeps no switch.
``attended_pair`` names the pair for the host.

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
from functools import partial
from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from tpufw.ops import paged_attend
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
    # Gated DeltaNet's [B, H, d_k, d_v] state (tpufw.models.olmo_hybrid
    # GatedDeltaNetLayer, beside the same ``conv_state``), in three of a
    # period's four layers; the fourth holds a page pair.
    "gdn_state": Role(STATE, 4),
    # A Mamba-1 mixer's [B, N, D] state (tpufw.models.phi4flash
    # MambaMixer, beside the same ``conv_state``): a decay a channel AND
    # a state, channels on the lanes.
    "mamba_state": Role(STATE, 3),
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


#: The row rungs under the whole pool, as shifts of B: B/8 alone, which
#: ``branch_pairs`` reads at the whole row alone. They serve the pools
#: whose decode step the kernel does not (``in_place``): the latent cache
#: (dsv2l: the measurements below), int8 arenas, verify blocks. Every branch is one
#: more copy of every attention layer in each of a pool's decode
#: programs (one a chunk length: five in a 16-step pool), and a warm
#: start loads them all. Measured on the chip (PR 38, DeepSeek-V2-Lite's
#: eight latent layers, 64 slots, 2-6 rows live; warm ``setup_s`` 51.2-
#: 56.2 s and ``tpot_p50_ms`` 20.1 before): B/8, B/4, B/2 by both key
#: rungs, six more branches a layer, +23.7 s with each branch a closure
#: and +11 s with each a cached program (``_read_rows``), TPOT 9.0 ms;
#: B/8 by both key rungs, two more, +3.5 s (6%), TPOT 9.0; B/8 at the
#: whole row, one more, level (53.7-56.1 s), TPOT 10.1. The gate on a
#: warm start is 10% and two cells stand under their best already.
ROW_SHIFTS = (3,)


def row_ladder(n_rows: int) -> Tuple[int, ...]:
    """The row counts a cached call under ``[B]`` cursors may read,
    ascending, the whole pool last: B >> k for ``ROW_SHIFTS`` where that
    is whole. A function of the pool's width alone, ``key_ladder``'s
    twin; each rung multiplies the branches of the programs that step a
    pool (a decode program a chunk length, not every cached program)."""
    b = int(n_rows)
    return tuple(
        b >> k for k in ROW_SHIFTS if b % (1 << k) == 0 and b >> k >= 1
    ) + (b,)


def branch_pairs(rows: Tuple[int, ...], keys: Tuple[int, ...]):
    """The (row count, key length) pairs a pool's cached call may read,
    ascending in key slots read: the rungs of ``rows`` under the whole
    pool at the whole row, then the whole pool at each rung of ``keys``."""
    return [(k, keys[-1]) for k in rows[:-1]] + [(rows[-1], n) for n in keys]


def branch_index(rows, keys, live_rows, live):
    """Index into ``branch_pairs`` of the pair that holds ``live_rows``
    live rows, the longest of ``live`` slots. Python ints (the host) or
    traced scalars (the program): one rule for both."""
    rung = key_rung(rows, live_rows)
    return rung + (rung == len(rows) - 1) * key_rung(keys, live)


def pool_ladders(max_seq_len: int, page: int, n_rows: int):
    """(row ladder, key ladder) of a cached call under ``[B]`` cursors
    that reads through the ladders (not ``in_place``: there the kernel
    reads each row by its own length, and Falcon-H1's pool, measured
    below, is served by it on the chip).
    A row of ONE key rung keeps one row rung too: its call has no switch,
    and putting one around the attention costs more than an eighth of so
    short a row saves (measured, PR 38: Falcon-H1's 32 slots x 2,048,
    six layers, 9 rows live: a step of 17.8 ms read 20.8 inside a switch
    of two branches, its ``tpot_p50_ms`` 20.1 read 23.2)."""
    keys = key_ladder(max_seq_len, page)
    rows = row_ladder(n_rows) if len(keys) > 1 else (int(n_rows),)
    return rows, keys


def attended_pair(cfg, n_rows: int, live_rows: int, live: int):
    """(rows, key slots of each) a cached call of ``cfg``'s model reads
    of a pool of ``n_rows`` when ``live_rows`` rows are live and the
    longest holds ``live`` slots, this call's tokens included: the
    branch the program's switch takes, for the host's count. One row
    (a prefill chunk, ``generate``: a scalar cursor) has the key rungs
    alone."""
    rows, keys = pool_ladders(
        cfg.max_seq_len, getattr(cfg, "kv_page", 0), n_rows
    )
    return branch_pairs(rows, keys)[
        branch_index(rows, keys, int(live_rows), int(live))
    ]


def in_place(cfg, leaves, width: int = 1) -> bool:
    """Whether a pool's cached call of ``width`` tokens a row reads the
    arena IN PLACE (tpufw.ops.paged_attend) where its ``attend`` offers
    that, and not through the ladders: from what the store can observe
    and nothing a caller sets. ``leaves`` are the PAGE leaves the call
    appends to (the host: the names in the pool's cache). One token a
    row; K/V heads in a paged arena that is not int8; no window over the
    arena (rings are ``ring_append``'s; a model that masks a window on
    its arena keeps the ladder in every layer, so that the host's count
    is one rule a call); and the chip, at the kernel's widths
    (``paged_attend.serves``): off it the ladder read runs."""
    page = getattr(cfg, "kv_page", 0)
    if width != 1 or not page or cfg.kv_quant == "int8":
        return False
    if set(leaves) != {"cached_key", "cached_value"}:
        return False
    if getattr(cfg, "sliding_window", None) is not None and not getattr(
        cfg, "window_ring", False
    ):
        return False
    stored = getattr(cfg, "kv_store_heads", None) or getattr(
        cfg, "n_kv_heads", 0
    )
    # A family that stores its heads under another view (paired heads:
    # tpufw.models.phi4flash) says how wide a stored head is.
    head_dim = getattr(cfg, "kv_store_head_dim", None) or getattr(
        cfg, "head_dim", 0
    )
    return bool(stored and head_dim) and paged_attend.serves(
        head_dim, page, stored, cfg.dtype
    )


def attended_slots(cfg, leaves, n_rows: int, lens, width: int = 1) -> int:
    """Key slots a cached call of ``cfg``'s model reads of a pool of
    ``n_rows`` whose live rows hold ``lens`` slots each, this call's
    tokens included: each live row's own pages where the call reads in
    place (``in_place``), else the pair of ``attended_pair``: the rule
    ``append``'s ``read`` follows, for the host's count."""
    lens = [int(n) for n in lens]
    if in_place(cfg, leaves, width):
        page = cfg.kv_page
        return sum(-(-n // page) * page for n in lens)
    return math.prod(
        attended_pair(cfg, n_rows, len(lens), max(lens, default=0))
    )


def page_readers(cfg) -> int:
    """Layers that read each PAGE pair of ``cfg``'s model in a cached
    call, its writer included: 1 wherever a layer reads only what it
    wrote; a family whose later layers attend pages an earlier one wrote
    (module docstring, READERS THAT ARE NOT THE WRITER) derives the
    count in its config (``kv_page_readers``). For the host, which books
    what the readers beside the writer read (tpufw.workloads.serve)."""
    return int(getattr(cfg, "kv_page_readers", 1))


def _head(x: jax.Array, n: int) -> jax.Array:
    """``x[:, :n]``; ``x`` itself where that is all of it."""
    return x if n == x.shape[1] else x[:, :n]


def _view(arenas, scales, ids, table, sel, *, length, page, dtype, heads=None):
    """(views, kv_segment_ids) of the first ``length`` logical slots of
    the rows ``sel`` (None: every row, in place): ``arenas`` maps PAGE
    leaf names to their values, ``ids`` is the SEGMENT leaf's, ``table``
    the page table's (paged: ``page`` > 0) and ``scales`` the int8
    arenas' scales by name (empty: not quantized). ``heads``: the
    model's heads where a slot holds more (``_stored_heads``); the view
    drops the rest."""
    def of(a):
        a = _head(a, length // page if page else length)
        return a if sel is None else a[sel]

    def shown(v):
        return v if heads is None else v[:, :, :heads]

    if not page:
        return {n: shown(of(a)) for n, a in arenas.items()}, of(ids)
    rows = of(table)
    shape = (rows.shape[0], length)
    views = {}
    for n, arena in arenas.items():
        pages = arena[rows]
        if scales:
            pages = dequantize_kv(pages, scales[n][rows], dtype)
        views[n] = shown(pages.reshape(shape + arena.shape[2:]))
    return views, ids[rows].reshape(shape)


def _stored_heads(cfg, new: Dict[str, jax.Array]):
    """(``new`` as a slot stores it, the model's head count for the view
    to cut back to, or None). A family whose config derives
    ``kv_store_heads`` (its K/V heads rounded up to whole tiles of 8
    sublanes: tpufw.models.olmo_hybrid) stores ``[B, t, H, hd]`` leaves
    with zero heads up to that count. The tiling pads 30 heads to 32 in
    HBM anyway, so the bytes are the same; what goes is a second copy of
    every arena leaf, which XLA:TPU otherwise makes and undoes in every
    decode call, keeping the arena in another dimension order inside the
    step loop than at the program's edge (measured at 30 heads, PERF.md
    section 7 (ap); Falcon-H1's 4 heads carry no such copy, so "does not
    fill a tile" is not the whole rule, and no family gets this unasked)."""
    held = getattr(cfg, "kv_store_heads", None)
    if not held:
        return new, None
    heads = next(iter(new.values())).shape[2]
    if held == heads:
        return new, None
    widen = ((0, 0), (0, 0), (0, held - heads), (0, 0))
    return {n: jnp.pad(x, widen) for n, x in new.items()}, heads


# tpulint: disable=TPU006 — nothing handed in is updated: what comes back
# is ``attend``'s output, scattered into zeros of the pool's width.
@partial(
    jax.jit,
    static_argnames=("attend", "k", "length", "page", "dtype", "heads"),
)
def _read_rows(
    attend, arenas, scales, ids, table, order, per_row,
    *, k, length, page, dtype, heads=None,
):
    """One branch of a pool's ``read``: ``attend`` over the first
    ``length`` slots of the first ``k`` rows of ``order`` [B], handed
    back at ``[B, ...]`` with zeros in the rows not read; k == B reads
    every row in place, whatever the order. A jitted function of its own, with
    ``attend`` among its static arguments: the layers of a model whose
    ``attend`` compare equal trace and lower each branch ONCE and call
    it, where a closure would be traced again in every layer (measured,
    PR 38: a warm start is mostly tracing, and four row rungs by two key
    rungs in eight layers of five decode programs cost dsv2l 24 s of
    it)."""
    view = partial(
        _view, arenas, scales, ids, table,
        length=length, page=page, dtype=dtype, heads=heads,
    )
    b = order.shape[0]
    if k == b:
        return attend(*view(None), per_row)
    sel = order[:k]
    out = attend(*view(sel), jax.tree_util.tree_map(lambda x: x[sel], per_row))
    return jax.tree_util.tree_map(
        lambda y: jnp.zeros((b,) + y.shape[1:], y.dtype)
        .at[sel].set(y, unique_indices=True),
        out,
    )


def append(module, cfg, new: Dict[str, jax.Array], segment_ids):
    """Append this call's tokens at the cache cursor; hand back how to
    read the cache: in place where ``in_place`` says so and ``attend``
    offers ``attend.paged`` (module docstring, WHO READS HOW), else under
    the live-prefix bound, as follows.

    Called from inside flax ``module``. ``new`` maps PAGE leaf names to
    ``[B, t, *feat]``; ``segment_ids`` [B, t] (None: all 1) are the
    tokens' own; a row whose ids are all 0 is not live (its slots are
    written all the same). Returns ``(read, segment_ids, q_slots)``: the
    queries' segment ids as stored, the logical slots the t queries sit
    at (``[B, t]``, or ``[1, t]`` under a scalar cursor) and ``read``,
    which runs the caller's contraction over the live prefix of the live
    rows: ``read(attend, per_row)`` is ``attend(views, kv_segment_ids,
    per_row)`` over K rows of the pool: each leaf's first L logical
    slots ``[K, L, *feat]`` (the new tokens included, in ``cfg.dtype``),
    the slots' ids ``[K, L]`` (0 = never written) and the same K rows of
    ``per_row``, a pytree of the caller's ``[B, ...]`` operands (the
    queries, their segment ids, ``q_slots``): what ``attend`` may not
    close over at width B. L is the rung of ``key_ladder`` that holds
    every live row, K the rung of ``row_ladder`` that holds the live
    rows, as ``branch_pairs`` pairs them (module docstring); under a
    scalar cursor K is B and ``per_row`` comes through as given.
    ``attend`` returns a pytree of ``[K, ...]`` arrays, which ``read``
    hands back at ``[B, ...]``, zero in the rows not read. It is traced
    at most once per (K, L), must return the same trailing shapes at
    each and may not touch flax variables; ``read`` touches none either
    (the writes are done when ``append`` returns), so it may be called
    again, by this module or by a later one that stores nothing of its
    own, with other queries at the same slots (module docstring, READERS
    THAT ARE NOT THE WRITER); where ``attend`` is hashable and
    compares equal across a model's layers (a frozen dataclass, not a
    closure) the layers share one trace of each branch (``_read_rows``).
    Query i may attend slot j iff ``j <= q_slots[., i]`` and the
    segments match: ``attention_mask(t, L, ...)``.
    """
    for name, x in new.items():
        if role(name) != Role(PAGE, x.ndim):
            raise ValueError(f"{name!r} rank {x.ndim} is not {role(name)}")
    new, heads = _stored_heads(cfg, new)
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

    state = (arenas, scale_of, ids, idx)
    how = dict(page=page, dtype=cfg.dtype, heads=heads)
    pool_rows, ladder = pool_ladders(s, page, b)
    if not cur.ndim:
        pool_rows = (b,)

    offer = cur.ndim == 1 and in_place(cfg, new, t)

    def read(attend: Callable, per_row=()):
        paged = getattr(attend, "paged", None) if offer else None
        if paged is not None:
            # One kernel over each live row's own pages, by its own
            # length (tpufw.ops.paged_attend): no rung, no gather.
            alive = jnp.any(seg > 0, axis=1)
            return paged(
                arenas, ids[idx].reshape(b, s), idx,
                jnp.where(alive, q_slots[:, -1] + 1, 0), per_row,
                heads=heads,
            )

        def whole(length: int):
            return attend(*_view(*state, None, length=length, **how), per_row)

        if len(ladder) == len(pool_rows) == 1:
            return whole(s)
        if cur.ndim == 0:
            return jax.lax.switch(
                key_rung(ladder, cur + t), [partial(whole, n) for n in ladder]
            )
        alive = jnp.any(seg > 0, axis=1)
        live = jnp.max(jnp.where(alive, q_slots[:, -1] + 1, 0))
        # Live rows first, each kind in the pool's own order. Outside
        # the switch: the layers of a step sort the same ids, which is
        # ONE sort to the compiler, where every branch of every layer
        # would hold its own; the whole pool's branches do not read it.
        order = jnp.argsort(~alive, stable=True)
        return jax.lax.switch(
            branch_index(pool_rows, ladder, jnp.sum(alive), live),
            [
                partial(
                    _read_rows, attend, *state, order, per_row,
                    k=k, length=n, **how,
                )
                for k, n in branch_pairs(pool_rows, ladder)
            ],
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
