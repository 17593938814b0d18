"""A pool's decode step over K/V pages in place (Pallas TPU).

``tpufw.ops.kv_store.append``'s ``read`` gathers the rows it attends into
a copy at one static length, written once and read twice more a layer,
however short the rows are. This kernel reads each row's OWN pages where
they lie in the arena, found through its own page-table row, as far as its
own cursor and no further; a row that is not live reads nothing. It
serves the call a pool's decode step makes: one query token a row, K/V
heads (``tpufw.models.llama._AttendHeads``), a paged arena that is not
int8. The mathematics and the precision are ``xla_attention``'s: operands
in the arena's dtype, float32 accumulation, ``1/sqrt(d)``, the optional
``tanh_soft_cap``, the mask ``slot <= q_slot AND kv segment == q
segment``, float32 max and sum, probabilities cast to the operands' dtype
for the value dot.

HOW A PAGE IS CONTRACTED. A page is ``[page, K, hd]`` (K stored kv heads)
and lands in VMEM as it lies in HBM, one DMA: ``page * K`` ROWS of ``hd``
lanes, a row a (slot, kv head) pair. Picking one head's keys out of that
is a sublane gather in VMEM or a DMA of half-sublane pieces (bf16 packs
two heads a sublane), so the kernel picks nothing: it takes a block of
pages as ONE key matrix ``[slots * K, hd]``, contracts ALL the query
heads ``[H, hd]`` with it on the MXU (``[H, slots * K]`` logits), and
masks every (query head, kv head) pair that is not the query head's own
to -1e30 beside the slots the causal and segment mask hides. Their
weights underflow to an exact 0.0, so the value dot ``[H, slots * K] x
[slots * K, hd]`` sums over the right head's slots alone. The MXU does K
times the multiplies a per-head contraction needs and is idle in a decode
step anyway: it takes a block's rows at about the rate HBM delivers them,
and G = 1 (30 heads of 30, stored 32) to G = 9 is the same code. The
arena's layout does not change and nothing of it is copied: the
``[n_pages, page, K, hd]`` leaf is handed over as ``[n_pages, page * K,
hd]``, the same bytes in the same order under XLA:TPU's tiling.

THE SEGMENT MASK. Whether a slot's stored segment id equals the row's
query's is one bit a slot. The caller gathers the ids through the table
(4 bytes a slot, where a key and a value are kilobytes) and packs the
comparison 32 slots a word; the words ride in SMEM beside the table and
the lengths (scalar prefetch), and a block expands its words over the K
lanes of each slot with a shift by a constant lane pattern.

THE PIPELINE. Grid over rows. A row's pages are read in blocks of
``block_rows`` key-matrix rows (2,048: 64 slots of 32 heads, 512 of 4),
every page of a block by its own DMA, all in flight at once, and the
next block's started before the current one is waited for (two buffers).
Online softmax over the blocks. Pages past the row's last are not
fetched: their buffer rows keep what an earlier block left (finite: the
value buffers start zeroed), under a mask that is false there.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpufw.ops.attention import tanh_soft_cap

NEG_INF = -1e30
_WORD = 32
#: Rows of the key matrix a block holds: 64 slots of 32 heads, 512 of 4.
#: Two buffers each of K and V at 2,048 x 128 bf16 are 2 MiB of VMEM, the
#: float32 logits of 32-72 query heads 0.25-0.6 MiB beside them. Measured
#: on the chip (PR 42, scripts/paged_attend_chip_check.py, us a call at
#: 2,048 / 4,096 / 8,192 rows): Olmo-Hybrid's pool 248 / 253 / 259,
#: Falcon-H1's 63 / 68 / 73 (a short row still contracts a whole block),
#: Solar-Open2's 90 / 95 / 95, Mixtral's 83 / 82 / 88, Laguna's 149 /
#: 150 / 155.
BLOCK_ROWS = 2048


def serves(head_dim: int, page: int, kv_heads: int, dtype) -> bool:
    """Whether the kernel is built for a page of ``page`` slots x
    ``kv_heads`` stored heads x ``head_dim``: on the TPU, at Mosaic's
    widths: a head of whole 128-lane vectors, a page of whole sublane
    tiles (16 rows of bfloat16, 8 of float32), and a head count XLA:TPU
    tiles without padding (a divisor or a multiple of the tile: at 30
    heads a slot's rows sit 32 apart in HBM, ``[n_pages, page * K, hd]``
    is other bytes than the leaf and handing it over would copy the
    arena). Off the chip (the rule ``tpufw.ops.flash.default_interpret``
    follows) the store's ladder read runs: it is the kernel's reference
    in the tests, which would otherwise step every pool through the
    Pallas interpreter."""
    tile = 8 * 4 // jnp.dtype(dtype).itemsize
    return (
        jax.default_backend() == "tpu"
        and head_dim % 128 == 0
        and (page * kv_heads) % tile == 0
        and (kv_heads % tile == 0 or tile % kv_heads == 0)
    )


def block_slots(page: int, kv_heads: int, block_rows: int = BLOCK_ROWS) -> int:
    """Key slots a block holds: whole pages and whole 32-slot words of
    the segment mask, about ``block_rows`` rows of the key matrix."""
    unit = math.lcm(page, _WORD)
    return unit * max(1, block_rows // (unit * kv_heads))


def pack_mask(ok: jax.Array, words: int) -> jax.Array:
    """``[B, S]`` bool -> ``[B, words]`` int32, slot j at bit j % 32 of
    word j // 32; slots past S read 0."""
    b, s = ok.shape
    ok = jnp.pad(ok, ((0, 0), (0, words * _WORD - s)))
    bits = ok.reshape(b, words, _WORD).astype(jnp.uint32) << jnp.arange(
        _WORD, dtype=jnp.uint32
    )
    return jax.lax.bitcast_convert_type(
        jnp.sum(bits, axis=-1, dtype=jnp.uint32), jnp.int32
    )


def _kernel(
    table_ref, lens_ref, bits_ref,  # scalar prefetch (SMEM)
    q_ref, bias_ref, slot_ref, k_hbm, v_hbm,
    o_ref,
    kbuf, vbuf, sems,
    *, page, rows_per_page, pages_per_block, pages_per_row, words_per_row,
    scale, soft_cap,
):
    b = pl.program_id(0)
    t_blk = pages_per_block * page
    words_per_block = t_blk // _WORD
    n_keys = lens_ref[b]
    n_pages = pl.cdiv(n_keys, page)
    n_blocks = pl.cdiv(n_keys, t_blk)

    @pl.when(b == 0)
    def _():
        # Rows of a block past the row's last page are never fetched:
        # what they hold meets a weight of exactly 0.0, so it must be
        # finite, which a buffer fresh from the allocator need not be.
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(blk, slot, i):
        at = table_ref[b * pages_per_row + blk * pages_per_block + i]
        rows = pl.ds(pl.multiple_of(i * rows_per_page, rows_per_page),
                     rows_per_page)
        return (
            pltpu.make_async_copy(
                k_hbm.at[at], kbuf.at[slot, rows], sems.at[0, slot]
            ),
            pltpu.make_async_copy(
                v_hbm.at[at], vbuf.at[slot, rows], sems.at[1, slot]
            ),
        )

    def each_page(blk, slot, what):
        held = jnp.minimum(n_pages - blk * pages_per_block, pages_per_block)

        def one(i, _):
            for copy in copies(blk, slot, i):
                what(copy)
            return _

        jax.lax.fori_loop(0, held, one, None)

    def fetch(blk, slot):
        each_page(blk, slot, lambda copy: copy.start())

    def wait(blk, slot):
        each_page(blk, slot, lambda copy: copy.wait())

    @pl.when(n_blocks > 0)
    def _():
        fetch(0, 0)

    q = q_ref[0]  # [H, hd]
    bias = bias_ref[...]  # [H, R]: 0 a query head's own kv head, else -1e30
    slot_of = slot_ref[...]  # [1, R]: the block's slot each column is of
    # The first 32 slots' columns: the shift that brings a column's bit
    # of its word to bit 0.
    word_cols = _WORD * (rows_per_page // page)
    shift = slot_of[:, :word_cols]

    def block(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            fetch(blk + 1, 1 - slot)

        wait(blk, slot)
        logits = jax.lax.dot_general(
            q, kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, R]
        if soft_cap is not None:
            logits = tanh_soft_cap(logits, soft_cap)
        word0 = b * words_per_row + blk * words_per_block
        same = jnp.concatenate(
            [
                (jnp.full(shift.shape, bits_ref[word0 + j]) >> shift) & 1
                for j in range(words_per_block)
            ],
            axis=1,
        )
        seen = (same == 1) & (slot_of + blk * t_blk < n_keys)
        logits = jnp.where(seen, logits + bias, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        v = vbuf[slot]
        acc = alpha * acc + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    h, hd = q.shape
    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, block,
        (
            jnp.full((h, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, hd), jnp.float32),
        ),
    )
    # A row that is not live read nothing: its sums are the zeros they
    # began as, and it gets zeros, as the ladder's rows not read.
    o_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _patterns(h_pad, heads, group, kv_heads, t_blk):
    """(bias [h_pad, R] float32, slot_of [1, R] int32) of a block of
    ``t_blk`` slots x ``kv_heads`` stored heads: column c is slot c //
    kv_heads, head c % kv_heads; query head h attends kv head h //
    group. Padded query heads (>= ``heads``) attend none."""
    cols = np.arange(t_blk * kv_heads)
    rows = np.arange(h_pad)
    own = (cols[None, :] % kv_heads == rows[:, None] // group) & (
        rows[:, None] < heads
    )
    bias = np.where(own, 0.0, NEG_INF).astype(np.float32)
    slot_of = (cols // kv_heads).astype(np.int32)[None, :]
    for shared in (bias, slot_of):  # cached: every caller's
        shared.setflags(write=False)
    return bias, slot_of


# Jitted of its own, as ``kv_store._read_rows`` is: a model's layers and a
# pool's decode programs share ONE trace and one lowered function a shape.
@functools.partial(
    jax.jit,
    static_argnames=("kv_heads", "logits_soft_cap", "interpret", "block_rows"),
)
def paged_attention(
    q: jax.Array,
    k_arena: jax.Array,
    v_arena: jax.Array,
    table: jax.Array,
    lens: jax.Array,
    same_segment: jax.Array,
    *,
    kv_heads: Optional[int] = None,
    logits_soft_cap: Optional[float] = None,
    interpret: bool = False,
    block_rows: int = BLOCK_ROWS,
) -> jax.Array:
    """One query token a row over the row's own pages, in place.

    ``q`` [B, H, hd]; the arenas ``[n_pages, page, K, hd]`` (K stored
    heads, of which the first ``kv_heads`` are the model's: default all);
    ``table`` [B, S / page] int32 maps a row's logical page to its
    arena page; ``lens`` [B] int32 is the key slots each row attends, its
    query's own included (``q_slot + 1``), 0 for a row that is not live;
    ``same_segment`` [B, S] bool says which logical slots carry the
    query's segment id. H = G x ``kv_heads``; query head h attends kv
    head h // G. Returns [B, H, hd] in ``q.dtype``: exact zeros for rows
    whose ``lens`` is 0."""
    b, h, hd = q.shape
    n_arena, page, stored, _ = k_arena.shape
    heads = stored if kv_heads is None else kv_heads
    if h % heads:
        raise ValueError(f"q heads {h} not divisible by kv heads {heads}")
    pages_per_row = table.shape[1]
    t_blk = block_slots(page, stored, block_rows)
    # The packed segment mask holds whole blocks a row.
    words_per_row = -(-pages_per_row * page // t_blk) * (t_blk // _WORD)
    rows_per_page = page * stored
    # Whole sublane tiles of query heads (16 rows of bf16, 8 of float32).
    h_pad = -(-h // 16) * 16
    bias, slot_of = _patterns(h_pad, h, h // heads, stored, t_blk)
    r = t_blk * stored
    kernel = functools.partial(
        _kernel,
        page=page,
        rows_per_page=rows_per_page,
        pages_per_block=t_blk // page,
        pages_per_row=pages_per_row,
        words_per_row=words_per_row,
        scale=1.0 / math.sqrt(hd),
        soft_cap=logits_soft_cap,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h_pad, hd), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((h_pad, r), lambda i, *_: (0, 0)),
                pl.BlockSpec((1, r), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h_pad, hd), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, r, hd), k_arena.dtype),
                pltpu.VMEM((2, r, hd), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_pad, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_attend",
    )(
        table.reshape(-1).astype(jnp.int32),
        lens.astype(jnp.int32),
        pack_mask(same_segment, words_per_row).reshape(-1),
        jnp.pad(q, ((0, 0), (0, h_pad - h), (0, 0))),
        jnp.asarray(bias),
        jnp.asarray(slot_of),
        k_arena.reshape(n_arena, rows_per_page, hd),
        v_arena.reshape(n_arena, rows_per_page, hd),
    )
    return out[:, :h]
