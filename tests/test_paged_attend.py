"""The paged decode kernel (tpufw.ops.paged_attend) under the Pallas
interpreter against ``xla_attention`` over the contiguous rows the pages
hold, and the host's count of what it reads against the kernel's own
rule. Small shapes; the chip's widths are ``scripts/
paged_attend_chip_check.py``'s and ``tests/test_program_text.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.ops import kv_store, paged_attend
from tpufw.ops.attention import xla_attention

PAGE, S, B = 4, 64, 6
#: Lengths a case's rows hold, the query's own slot included: not live,
#: one slot, whole pages, one past them, the whole row, and one between.
LENS = (0, 1, 3 * PAGE, 3 * PAGE + 1, S, 37)


@dataclasses.dataclass(frozen=True)
class Case:
    heads: int
    kv_heads: int
    stored: int = 0  # heads a page holds (0: kv_heads)
    hd: int = 16
    dtype: str = "float32"
    soft_cap: float = None
    pad: bool = False  # segment-0 slots inside the rows
    share: bool = False  # rows 3 and 4 share their first pages
    shuffle: bool = True  # the table scattered over the arena
    block_rows: int = 64  # several blocks a row, the last one partial
    tol: float = 2e-5  # tests/test_flash.py's


CASES = {
    "g1_30_heads_stored_32": Case(30, 30, stored=32, block_rows=512),
    "g4": Case(8, 2),
    # tpufw.models.phi4flash: 40 zero-padded query heads over 10 K/V
    # pairs, a page of 16 stored heads (six of zeros).
    "g4_10_pairs_stored_16": Case(40, 10, stored=16, block_rows=256),
    "g5": Case(20, 4, block_rows=128),
    "g9": Case(18, 2),
    "soft_cap": Case(8, 2, soft_cap=3.0),
    "pad_slots_inside_rows": Case(8, 2, pad=True),
    "rows_sharing_prefix_pages": Case(8, 2, share=True),
    "table_in_arena_order": Case(8, 2, shuffle=False),
    "one_block_a_row": Case(8, 2, block_rows=paged_attend.BLOCK_ROWS),
    "bfloat16": Case(8, 4, dtype="bfloat16", tol=1e-2),
}


def _build(c: Case, seed=0):
    """(q, arenas, table, lens, kv_seg, q_seg, contiguous k, v)."""
    rng = np.random.default_rng(seed)
    stored = c.stored or c.kv_heads
    dtype = jnp.dtype(c.dtype)
    k = rng.standard_normal((B, S, stored, c.hd), np.float32)
    v = rng.standard_normal((B, S, stored, c.hd), np.float32)
    # Heads a page holds beyond the model's are zeros (_stored_heads).
    k[:, :, c.kv_heads:] = 0
    v[:, :, c.kv_heads:] = 0
    per_row = S // PAGE
    n_pages = B * per_row + 1
    order = rng.permutation(n_pages - 1) if c.shuffle else np.arange(n_pages - 1)
    table = 1 + order[: B * per_row].reshape(B, per_row)
    if c.share:
        # A prefix trie's pages: both rows' tables name the same ones,
        # so both rows hold the same keys there.
        table[4, :5] = table[3, :5]
        k[4, : 5 * PAGE], v[4, : 5 * PAGE] = k[3, : 5 * PAGE], v[3, : 5 * PAGE]
    kv_seg = np.ones((B, S), np.int32)
    if c.pad:
        kv_seg[:, :3] = 0  # a left pad
        kv_seg[:, 9:11] = 0  # and a hole
        kv_seg[2, :] = 2  # a row of another segment than its neighbours
    lens = np.asarray(LENS, np.int32)
    q_seg = kv_seg[np.arange(B), np.maximum(lens - 1, 0)]
    # Page 0 is the junk sink: never in a table, never read.
    k_arena = rng.standard_normal((n_pages, PAGE, stored, c.hd), np.float32)
    v_arena = rng.standard_normal((n_pages, PAGE, stored, c.hd), np.float32)
    k_arena[table] = k.reshape(B, per_row, PAGE, stored, c.hd)
    v_arena[table] = v.reshape(B, per_row, PAGE, stored, c.hd)
    q = rng.standard_normal((B, c.heads, c.hd), np.float32)
    cast = lambda x: jnp.asarray(x, dtype)
    return (cast(q), cast(k_arena), cast(v_arena), jnp.asarray(table, jnp.int32),
            jnp.asarray(lens), jnp.asarray(kv_seg), jnp.asarray(q_seg),
            cast(k[:, :, : c.kv_heads]), cast(v[:, :, : c.kv_heads]))


def _kernel(c: Case, q, k_arena, v_arena, table, lens, kv_seg, q_seg):
    return paged_attend.paged_attention(
        q, k_arena, v_arena, table, lens, kv_seg == q_seg[:, None],
        kv_heads=c.kv_heads, logits_soft_cap=c.soft_cap, interpret=True,
        block_rows=c.block_rows,
    )


@pytest.mark.parametrize("name", CASES)
def test_kernel_is_xla_attention_over_the_rows_the_pages_hold(name):
    c = CASES[name]
    q, k_arena, v_arena, table, lens, kv_seg, q_seg, k, v = _build(c)
    got = _kernel(c, q, k_arena, v_arena, table, lens, kv_seg, q_seg)
    want = xla_attention(
        q[:, None], k, v, causal=True, segment_ids=q_seg[:, None],
        kv_segment_ids=kv_seg, q_positions=jnp.maximum(lens - 1, 0)[:, None],
        logits_soft_cap=c.soft_cap,
    )[:, 0]
    assert got.shape == want.shape and got.dtype == q.dtype
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=c.tol, rtol=c.tol,
    )
    # Rows that are not live read nothing and come back exact zeros,
    # as the ladder's rows not read.
    assert not np.asarray(got, np.float32)[~live].any()


def test_kernel_reads_each_row_s_own_pages_and_no_other():
    """Every page the rows' lengths do not reach holds NaN, page 0 and
    the pages past a row's last among them: the result is the same. A
    NaN in a page a row does hold reaches that row alone."""
    c = CASES["g4"]
    q, k_arena, v_arena, table, lens, kv_seg, q_seg, _, _ = _build(c)
    clean = _kernel(c, q, k_arena, v_arena, table, lens, kv_seg, q_seg)
    held = np.zeros(k_arena.shape[0], bool)
    for row, n in zip(np.asarray(table), np.asarray(lens)):
        held[row[: -(-int(n) // PAGE)]] = True
    poison = lambda a: jnp.where(held[:, None, None, None], a, jnp.nan)
    got = _kernel(
        c, q, poison(k_arena), poison(v_arena), table, lens, kv_seg, q_seg
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    last = int(np.asarray(table)[3, 3])  # row 3's fourth page: its last
    got = _kernel(
        c, q, k_arena, v_arena.at[last].set(jnp.nan), table, lens, kv_seg, q_seg
    )
    bad = np.isnan(np.asarray(got)).any(axis=(1, 2))
    assert bad.tolist() == [False, False, False, True, False, False]


def _cfg(**over):
    from tpufw.models.llama import LlamaConfig

    base = dict(
        vocab_size=64, d_model=256, n_layers=1, n_heads=4, n_kv_heads=2,
        head_dim=128, d_ff=64, max_seq_len=4096, kv_page=16, kv_pages=1025,
        dtype=jnp.bfloat16,
    )
    return LlamaConfig(**{**base, **over})


KV = ("cached_key", "cached_value")


@pytest.mark.parametrize("seed", range(4))
def test_host_counts_the_pages_the_kernel_visits(seed, monkeypatch):
    """For random cursors and liveness the host's count is the kernel's:
    a row's ``lens`` (its cursor + 1 where it is live, else 0) in whole
    pages, the bound of the kernel's page loop; and it is the ladders'
    pair wherever the ladder serves the call."""
    from tpufw.infer.pages import PagedSlotPool

    rng = np.random.default_rng(seed)
    cfg, b = _cfg(), 16
    cursors = rng.integers(0, cfg.max_seq_len, b)
    alive = rng.random(b) < 0.4
    lens = np.where(alive, cursors + 1, 0)  # what ``read`` hands the kernel
    live = [int(n) for n in lens if n]

    pool = PagedSlotPool.__new__(PagedSlotPool)  # the count needs no device state
    pool.model = pool.row_model = type("M", (), {"cfg": cfg})
    pool.n_slots, pool.page_leaves = b, frozenset(KV)
    whole = b * cfg.max_seq_len
    pair = lambda: (
        int(np.prod(kv_store.attended_pair(cfg, b, len(live), max(live, default=0)))),
        whole,
    )
    # Off the chip the ladder serves every call.
    assert not kv_store.in_place(cfg, KV)
    assert pool.attended_keys([live]) == pair()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kv_store.in_place(cfg, KV)
    visited = sum(int(paged_attend.pl.cdiv(int(n), cfg.kv_page)) for n in lens)
    assert pool.attended_keys([live]) == (visited * cfg.kv_page, whole)
    # A verify block, a prefill chunk's row and a latent cache stay on
    # the ladders.
    assert pool.attended_keys([live], width=5) == pair()
    assert pool.attended_keys([live[:1]], chunk=True) == (
        int(np.prod(kv_store.attended_pair(cfg, 1, 1, max(live[:1], default=0)))),
        cfg.max_seq_len,
    )
    pool.page_leaves = frozenset(("cached_ckv", "cached_kpe"))
    assert pool.attended_keys([live]) == pair()


@pytest.mark.parametrize(
    "why, cfg, leaves, width",
    [
        ("int8 arena", dict(kv_quant="int8"), KV, 1),
        ("contiguous pool", dict(kv_page=0, kv_pages=0), KV, 1),
        ("a window masked on the arena", dict(sliding_window=512), KV, 1),
        ("a head narrower than a lane vector", dict(head_dim=64), KV, 1),
        ("a page of less than a sublane tile", dict(n_kv_heads=1, kv_page=8), KV, 1),
        ("a head count the arena's tiling pads", dict(n_kv_heads=30), KV, 1),
        ("latent cache", {}, ("cached_ckv", "cached_kpe"), 1),
        ("a verify block", {}, KV, 4),
    ],
)
def test_what_stays_on_the_ladders_on_the_chip(why, cfg, leaves, width, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kv_store.in_place(_cfg(), KV)
    assert not kv_store.in_place(_cfg(**cfg), leaves, width), why


def test_a_pool_steps_through_the_kernel_as_through_the_ladder(monkeypatch):
    """A paged pool's decode steps with the kernel in ``read`` (steered
    here: the interpreter, tiny widths) serve the tokens the ladder read
    serves, in float32, where the two differ by rounding order alone."""
    from tests import test_pages as tp

    seam = tp._seam_family

    def seam32(family):
        cls, cfg = seam(family)
        return cls, dataclasses.replace(cfg, dtype=jnp.float32)

    monkeypatch.setattr(tp, "_seam_family", seam32)
    prompts = [
        np.random.default_rng(5).integers(1, 200, 300).tolist(), [1, 5, 9],
    ]

    def serve(kernel: bool):
        calls = []
        if kernel:
            real = paged_attend.paged_attention

            def interpreted(*a, **k):
                calls.append(a[0].shape)
                return real(*a, interpret=True, block_rows=256, **k)

            monkeypatch.setattr(paged_attend, "serves", lambda *a: True)
            monkeypatch.setattr(paged_attend, "paged_attention", interpreted)
        _, _, pool = tp._long_family("llama", monkeypatch)
        firsts = {}
        for i, p in enumerate(prompts):
            firsts[i], _ = tp._admit(pool, i, p, i, max_new=12)
        rows = tp._decode_all(pool, firsts, max_new=12, chunk=4)
        return [rows[0], rows[1]], calls, pool

    want, none, _ = serve(False)
    jax.clear_caches()  # equal models share a trace: this one is steered
    got, calls, pool = serve(True)
    jax.clear_caches()  # and is no later test's
    assert not none and calls  # traced into the decode programs
    assert got == want
    # And the host books each live row's own pages for such a step.
    assert pool.attended_keys([[301, 4]]) == (304 + tp.PAGE, 2 * tp.LONG_S)
