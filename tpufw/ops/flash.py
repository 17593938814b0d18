"""Pallas TPU flash attention (forward + backward), FlashAttention-2 style.

Replaces the O(T*S) materialized-logits attention with blockwise online
softmax in VMEM: per (batch, head, q-block) the kernel streams K/V blocks
from VMEM-resident [S, D] slabs, keeping running max/sum statistics. This is
the memory lever that lets the single-chip bench run larger batches (the
xla backend's [B, H, T, S] fp32 logits were the OOM driver) and the building
block the ring (sequence-parallel) backend reuses per shard.

Layout notes (see /opt/skills/guides/pallas_guide.md):
- blocks are (bq, D) / (bkv, D) with D=head_dim (128 for Llama) — lane dim
  aligned; bq/bkv are 128 multiples; inputs are padded to block multiples
  and masked via static-shape iota comparisons.
- GQA never materializes repeated K/V: the kv BlockSpec index_map divides
  the head index (h // rep) so all rep query heads stream the same slab.
- softmax statistics accumulate in fp32; matmuls request
  preferred_element_type=f32 so the MXU accumulates in fp32 from bf16 inputs.
- packed batches: int32 segment ids ([B, T] query-side, [B, S] key-side)
  stream alongside q/k and add a same-segment term to the mask, so the
  packed-corpus data path (tpufw.train.native_data emits segment_ids) keeps
  the flash kernel instead of falling back to materialized logits. Padded
  positions carry segment 0 on both sides; cross-segment and pad→real
  attention are both cut by the equality test.

Backward recomputes P from (q, k, lse) — the flash trick — in two kernels:
dq (grid over q blocks) and dk/dv (grid over kv blocks, per *query* head,
summed over the GQA group outside the kernel).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpufw.ops.attention import announce_once, tanh_soft_cap

NEG_INF = -1e30

# Mosaic tiling: the last two dims of every block must be (divisible by 8,
# divisible by 128) or equal to the array dims. 2-D [B, T] segment-id
# arrays can't satisfy that (a (1, bq) block has sublane size 1), so they
# ship lanes/sublanes-broadcast — query ids as [B, T, LANES] blocks
# (bq, 128), kv ids as [B, SUBLANES, S] blocks (8, bkv) — the layout the
# official TPU flash kernel uses. Caught on real hardware in round 2: the
# CPU interpreter never enforces tiling, so tests alone missed it.
_LANES = 128
_SUBLANES = 8


def _qseg_lanes(qseg_p: jax.Array) -> jax.Array:
    b, t_p = qseg_p.shape
    return jnp.broadcast_to(qseg_p[:, :, None], (b, t_p, _LANES))


def _kseg_sublanes(kseg_p: jax.Array) -> jax.Array:
    b, s_p = kseg_p.shape
    return jnp.broadcast_to(kseg_p[:, None, :], (b, _SUBLANES, s_p))


def _seg_mask(qseg_block: jax.Array, kseg_row: jax.Array) -> jax.Array:
    """[bq, LANES] lanes-broadcast q ids x [1, bkv] kv ids -> [bq, bkv]."""
    bkv = kseg_row.shape[-1]
    return jnp.tile(qseg_block, (1, bkv // _LANES)) == kseg_row


# Scoped VMEM one kernel may take by default on a v5e. The forward and dq
# kernels keep the whole K and V sequence resident per grid step, the dkv
# kernel the whole Q and dO, and the pipeline double-buffers each:
# 4 * S * D * itemsize bytes. Measured on the chip (PR 21,
# scripts/flash_chip_check.py, bf16, D=128): S=8192 (8 MiB) compiles and
# runs, forward and backward, with and without segment ids; S=16384 asks
# for 16.04 MiB and Mosaic refuses it.
_VMEM_SCOPED_BYTES = 16 * 2**20


def _check_slabs_fit(n_pad: int, d: int, dtype) -> None:
    """Refuse at trace time a sequence whose resident slabs Mosaic would
    refuse at compile time, with a message that says what to do."""
    need = 4 * n_pad * d * jnp.dtype(dtype).itemsize
    if need >= _VMEM_SCOPED_BYTES:
        raise ValueError(
            f"flash attention keeps whole-sequence K/V (Q/dO in backward) "
            f"slabs in VMEM: {n_pad} positions x head_dim {d} need "
            f"{need / 2**20:.0f} MiB double-buffered, the kernel may take "
            f"{_VMEM_SCOPED_BYTES / 2**20:.0f} MiB. Shard the sequence "
            "(attention_backend='ring' or 'ulysses' over the `sequence` "
            "mesh axis) so each shard is shorter; a kernel that streams "
            "K/V blocks from HBM is not written yet."
        )


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _causal_mask(i_block, j_block, bq, bkv, offset):
    """[bq, bkv] bool mask: query global pos (+offset) >= key global pos."""
    q_pos = i_block * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0
    ) + offset
    k_pos = j_block * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    return q_pos >= k_pos


def _first_kv_block(i_block, bq, bkv, offset, window):
    """First kv block a sliding-window query block can see (0 without a
    window): the block holding position q_pos_min - window + 1. Blocks
    before it are fully masked — skipping them is where local attention's
    FLOP/bandwidth savings actually come from (the mask alone only zeroes
    already-done work)."""
    if window is None:
        return 0
    lo = i_block * bq + offset - window + 1
    return jnp.maximum(jax.lax.div(lo, bkv), 0)


def _window_mask(i_block, j_block, bq, bkv, offset, window):
    """[bq, bkv] bool mask: key within ``window`` positions of the query
    (sliding-window / local attention, Gemma-style)."""
    q_pos = i_block * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0
    ) + offset
    k_pos = j_block * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    return (q_pos - k_pos) < window


def _fwd_kernel(
    *refs, bq, bkv, s_actual, causal, offset, scale, has_seg, soft_cap,
    window,
):
    if has_seg:
        q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref = refs
        qseg = qseg_ref[0]  # [bq, LANES]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        kseg_ref = qseg = None
    i = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, D]
    n_kv = k_ref.shape[2] // bkv

    def body(j, carry):
        m_prev, l_prev, acc = carry
        start = pl.multiple_of(j * bkv, bkv)
        k = k_ref[0, 0, pl.ds(start, bkv), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(start, bkv), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bkv]
        if soft_cap is not None:
            # Applied before masking: cap(NEG_INF) would squash the mask.
            logits = tanh_soft_cap(logits, soft_cap)
        k_pos = j * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 1
        )
        mask = k_pos < s_actual
        if causal:
            mask = mask & _causal_mask(i, j, bq, bkv, offset)
        if window is not None:
            mask = mask & _window_mask(i, j, bq, bkv, offset, window)
        if has_seg:
            kseg = kseg_ref[0, :1, pl.ds(start, bkv)]  # [1, bkv]
            mask = mask & _seg_mask(qseg, kseg)
        logits = jnp.where(mask, logits, NEG_INF)
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc

    d = q_ref.shape[-1]
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        # Only stream kv blocks that intersect the causal triangle.
        n_needed = jax.lax.div(
            (i + 1) * bq + offset + bkv - 1, bkv
        )
        n_iter = jnp.minimum(n_needed, n_kv)
    else:
        n_iter = n_kv
    j0 = _first_kv_block(i, bq, bkv, offset, window)
    m, l, acc = jax.lax.fori_loop(j0, n_iter, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = (m + jnp.log(l_safe))[:, 0]


def _dq_kernel(
    *refs, bq, bkv, s_actual, causal, offset, scale, has_seg, soft_cap,
    window,
):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         qseg_ref, kseg_ref, dq_ref) = refs
        qseg = qseg_ref[0]  # [bq, LANES]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref) = refs
        kseg_ref = qseg = None
    i = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0][:, None]  # [bq, 1]
    delta = delta_ref[0, 0, 0][:, None]
    n_kv = k_ref.shape[2] // bkv

    def body(j, dq):
        start = pl.multiple_of(j * bkv, bkv)
        k = k_ref[0, 0, pl.ds(start, bkv), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(start, bkv), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        k_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = k_pos < s_actual
        if causal:
            mask = mask & _causal_mask(i, j, bq, bkv, offset)
        if window is not None:
            mask = mask & _window_mask(i, j, bq, bkv, offset, window)
        if has_seg:
            kseg = kseg_ref[0, :1, pl.ds(start, bkv)]
            mask = mask & _seg_mask(qseg, kseg)
        if soft_cap is not None:
            capped = tanh_soft_cap(logits, soft_cap)
        else:
            capped = logits
        p = jnp.where(mask, jnp.exp(capped - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        if soft_cap is not None:
            # d(cap*tanh(x/cap))/dx = 1 - tanh^2 = 1 - (capped/cap)^2.
            ds = ds * (1.0 - (capped / soft_cap) ** 2)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    d = q_ref.shape[-1]
    if causal:
        n_needed = jax.lax.div((i + 1) * bq + offset + bkv - 1, bkv)
        n_iter = jnp.minimum(n_needed, n_kv)
    else:
        n_iter = n_kv
    j0 = _first_kv_block(i, bq, bkv, offset, window)
    dq = jax.lax.fori_loop(
        j0, n_iter, body, jnp.zeros((bq, d), jnp.float32)
    )
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    *refs, bq, bkv, t_actual, causal, offset, scale, has_seg, soft_cap,
    window,
):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         qseg_ref, kseg_ref, dk_ref, dv_ref) = refs
        kseg = kseg_ref[0, :1, :]  # [1, bkv]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref) = refs
        qseg_ref = kseg = None
    j = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)  # [bkv, D]
    v = v_ref[0, 0].astype(jnp.float32)
    n_q = q_ref.shape[2] // bq

    def body(i, carry):
        dk, dv = carry
        start = pl.multiple_of(i * bq, bq)
        q = q_ref[0, 0, pl.ds(start, bq), :].astype(jnp.float32) * scale
        do = do_ref[0, 0, pl.ds(start, bq), :].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, pl.ds(start, bq)][:, None]
        delta = delta_ref[0, 0, 0, pl.ds(start, bq)][:, None]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        mask = q_pos < t_actual
        if causal:
            mask = mask & _causal_mask(i, j, bq, bkv, offset)
        if window is not None:
            mask = mask & _window_mask(i, j, bq, bkv, offset, window)
        if has_seg:
            qseg = qseg_ref[0, pl.ds(start, bq), :]  # [bq, LANES]
            mask = mask & _seg_mask(qseg, kseg)
        if soft_cap is not None:
            capped = tanh_soft_cap(logits, soft_cap)
        else:
            capped = logits
        p = jnp.where(mask, jnp.exp(capped - lse), 0.0)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        if soft_cap is not None:
            ds = ds * (1.0 - (capped / soft_cap) ** 2)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    if causal:
        # q blocks strictly before this kv block never attend to it.
        first = jax.lax.div(j * bkv - offset, bq)
        i0 = jnp.maximum(first, 0)
    else:
        i0 = 0
    if window is not None:
        # q blocks entirely beyond the window never see this kv block:
        # the largest visible q_pos is (j+1)*bkv - 1 + window - 1.
        last_q = j * bkv + bkv - 1 + window - 1 - offset
        i_hi = jnp.minimum(jax.lax.div(last_q, bq) + 1, n_q)
        i_hi = jnp.maximum(i_hi, i0)  # never negative-length loops
    else:
        i_hi = n_q
    d = k_ref.shape[-1]
    dk0 = jnp.zeros((bkv, d), jnp.float32)
    dv0 = jnp.zeros((bkv, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(i0, i_hi, body, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _heads_layout(q, k, v):
    """[B,T,H,D] -> [B,H,T,D] for all three."""
    return (
        jnp.transpose(q, (0, 2, 1, 3)),
        jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)),
    )


def _env_block(name: str) -> int | None:
    from tpufw.workloads.env import env_opt_int

    return env_opt_int(name)


def _check_block(b: int, n_pad: int, axis: str, source: str) -> int:
    """Validate an explicit block-size override: the grid and the
    in-kernel kv loop both assume EXACT tiling of the padded length, and
    the lanes-broadcast segment masks assume 128 multiples."""
    if b % 128 or b <= 0:
        raise ValueError(
            f"flash {axis} block {b} (from {source}) must be a positive "
            "multiple of 128 (Mosaic lane tiling; segment masks "
            "broadcast in 128-lane tiles)"
        )
    if n_pad % b:
        raise ValueError(
            f"flash {axis} block {b} (from {source}) must divide the "
            f"padded sequence length {n_pad}; pick a 128-multiple "
            f"divisor of {n_pad} (e.g. {math.gcd(b, n_pad)})"
        )
    return b


def _block_sizes(t_pad, s_pad, override=None):
    """Block sizes for the (q, kv) grid. Default: the largest sizes
    (<=512) that DIVIDE the padded lengths — the grid and the in-kernel
    kv loop both assume exact tiling (inputs are padded to 128
    multiples, so 128 always divides).

    ``override`` is an explicit (bq, bkv) pair (either element None =
    heuristic); with no override the TPUFW_FLASH_BQ / TPUFW_FLASH_BKV
    env vars apply — the autotuner's lever (tpufw.tune), also usable
    standalone. Overrides are validated against the padded lengths with
    a clear error rather than silently mistiling."""

    def pick(n):
        for b in (512, 256, 128):
            if n % b == 0:
                return b
        return n  # n < 128 can't happen post-padding; defensive.

    bq, bkv = (override or (None, None))
    src_q, src_kv = "block_sizes kwarg", "block_sizes kwarg"
    if bq is None and (e := _env_block("flash_bq")) is not None:
        bq, src_q = e, "TPUFW_FLASH_BQ"
    if bkv is None and (e := _env_block("flash_bkv")) is not None:
        bkv, src_kv = e, "TPUFW_FLASH_BKV"
    bq = pick(t_pad) if bq is None else _check_block(bq, t_pad, "q", src_q)
    bkv = (
        pick(s_pad) if bkv is None else _check_block(bkv, s_pad, "kv", src_kv)
    )
    return bq, bkv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9)
)
def _flash(
    q, k, v, qseg, kseg, causal, interpret, soft_cap, window, block_sizes
):
    out, _ = _flash_fwd_impl(
        q, k, v, qseg, kseg, causal, interpret, soft_cap, window,
        block_sizes=block_sizes,
    )
    return out


def _flash_fwd_impl(
    q, k, v, qseg, kseg, causal, interpret, soft_cap, window=None,
    offset=None, block_sizes=None,
):
    """``offset``: query i sits at absolute position offset+i relative
    to the keys. Default s - t (decode alignment); ring attention passes
    the static chunk distance step*L so window masks see GLOBAL
    positions (tpufw.parallel.ring_flash)."""
    b, t, h, d = q.shape
    _, s, kh, _ = k.shape
    rep = h // kh
    scale = 1.0 / math.sqrt(d)
    if offset is None:
        offset = s - t
    has_seg = qseg is not None

    qh, kh_, vh = _heads_layout(q, k, v)
    t_pad_mult = 128
    qh = _pad_to(qh, 2, t_pad_mult)
    kh_ = _pad_to(kh_, 2, t_pad_mult)
    vh = _pad_to(vh, 2, t_pad_mult)
    t_p, s_p = qh.shape[2], kh_.shape[2]
    if not interpret:
        _check_slabs_fit(max(t_p, s_p), d, q.dtype)
    bq, bkv = _block_sizes(t_p, s_p, block_sizes)

    grid = (b, h, t_p // bq)
    kernel = functools.partial(
        _fwd_kernel,
        bq=bq,
        bkv=bkv,
        s_actual=s,
        causal=causal,
        offset=offset,
        scale=scale,
        has_seg=has_seg,
        soft_cap=soft_cap,
        window=window,
    )
    in_specs = [
        pl.BlockSpec(
            (1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)
        ),
        pl.BlockSpec(
            (1, 1, s_p, d), lambda b_, h_, i: (b_, h_ // rep, 0, 0)
        ),
        pl.BlockSpec(
            (1, 1, s_p, d), lambda b_, h_, i: (b_, h_ // rep, 0, 0)
        ),
    ]
    inputs = [qh, kh_, vh]
    if has_seg:
        # Pad with segment 0 == the padding segment on both sides.
        qseg_p = _pad_to(qseg.astype(jnp.int32), 1, t_pad_mult)
        kseg_p = _pad_to(kseg.astype(jnp.int32), 1, t_pad_mult)
        in_specs += [
            pl.BlockSpec(
                (1, bq, _LANES), lambda b_, h_, i: (b_, i, 0)
            ),
            pl.BlockSpec(
                (1, _SUBLANES, s_p), lambda b_, h_, i: (b_, 0, 0)
            ),
        ]
        inputs += [_qseg_lanes(qseg_p), _kseg_sublanes(kseg_p)]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, 1, bq), lambda b_, h_, i: (b_, h_, 0, i)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t_p, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, t_p), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    out_bthd = jnp.transpose(out[:, :, :t, :], (0, 2, 1, 3))
    return out_bthd, (q, k, v, qseg, kseg, out_bthd, lse)


def _flash_bwd_impl(
    causal, interpret, soft_cap, window, res, g, offset=None,
    block_sizes=None,
):
    q, k, v, qseg, kseg, out, lse = res
    b, t, h, d = q.shape
    _, s, kh, _ = k.shape
    rep = h // kh
    scale = 1.0 / math.sqrt(d)
    if offset is None:
        offset = s - t
    has_seg = qseg is not None

    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [B, T, H]
    delta = jnp.transpose(delta, (0, 2, 1))[:, :, None, :]  # [B,H,1,T]

    qh, kh_, vh = _heads_layout(q, k, v)
    doh = jnp.transpose(g, (0, 2, 1, 3))
    qh = _pad_to(qh, 2, 128)
    kh_ = _pad_to(kh_, 2, 128)
    vh = _pad_to(vh, 2, 128)
    doh = _pad_to(doh, 2, 128)
    delta_p = _pad_to(delta, 3, 128)
    lse_p = lse  # stored padded in the residual
    t_p, s_p = qh.shape[2], kh_.shape[2]
    bq, bkv = _block_sizes(t_p, s_p, block_sizes)
    if has_seg:
        qseg_l = _qseg_lanes(_pad_to(qseg.astype(jnp.int32), 1, 128))
        kseg_s = _kseg_sublanes(_pad_to(kseg.astype(jnp.int32), 1, 128))

    # dq: grid over q blocks.
    dq_in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec(
            (1, 1, s_p, d), lambda b_, h_, i: (b_, h_ // rep, 0, 0)
        ),
        pl.BlockSpec(
            (1, 1, s_p, d), lambda b_, h_, i: (b_, h_ // rep, 0, 0)
        ),
        pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec(
            (1, 1, 1, bq), lambda b_, h_, i: (b_, h_, 0, i)
        ),
        pl.BlockSpec(
            (1, 1, 1, bq), lambda b_, h_, i: (b_, h_, 0, i)
        ),
    ]
    dq_inputs = [qh, kh_, vh, doh, lse_p, delta_p]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec(
                (1, bq, _LANES), lambda b_, h_, i: (b_, i, 0)
            ),
            pl.BlockSpec(
                (1, _SUBLANES, s_p), lambda b_, h_, i: (b_, 0, 0)
            ),
        ]
        dq_inputs += [qseg_l, kseg_s]
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel,
            bq=bq,
            bkv=bkv,
            s_actual=s,
            causal=causal,
            offset=offset,
            scale=scale,
            has_seg=has_seg,
            soft_cap=soft_cap,
            window=window,
        ),
        grid=(b, h, t_p // bq),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, t_p, d), q.dtype),
        interpret=interpret,
    )(*dq_inputs)

    # dk/dv: grid over kv blocks, per *query* head; GQA-summed after.
    dkv_in_specs = [
        pl.BlockSpec((1, 1, t_p, d), lambda b_, h_, j: (b_, h_, 0, 0)),
        pl.BlockSpec(
            (1, 1, bkv, d), lambda b_, h_, j: (b_, h_ // rep, j, 0)
        ),
        pl.BlockSpec(
            (1, 1, bkv, d), lambda b_, h_, j: (b_, h_ // rep, j, 0)
        ),
        pl.BlockSpec((1, 1, t_p, d), lambda b_, h_, j: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, 1, t_p), lambda b_, h_, j: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, 1, t_p), lambda b_, h_, j: (b_, h_, 0, 0)),
    ]
    dkv_inputs = [qh, kh_, vh, doh, lse_p, delta_p]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec(
                (1, t_p, _LANES), lambda b_, h_, j: (b_, 0, 0)
            ),
            pl.BlockSpec(
                (1, _SUBLANES, bkv), lambda b_, h_, j: (b_, 0, j)
            ),
        ]
        dkv_inputs += [qseg_l, kseg_s]
    dk_full, dv_full = pl.pallas_call(
        functools.partial(
            _dkv_kernel,
            bq=bq,
            bkv=bkv,
            t_actual=t,
            causal=causal,
            offset=offset,
            scale=scale,
            has_seg=has_seg,
            soft_cap=soft_cap,
            window=window,
        ),
        grid=(b, h, s_p // bkv),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bkv, d), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda b_, h_, j: (b_, h_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_p, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s_p, d), jnp.float32),
        ],
        interpret=interpret,
    )(*dkv_inputs)

    dq = jnp.transpose(dq[:, :, :t, :], (0, 2, 1, 3))
    dk = dk_full[:, :, :s, :].reshape(b, kh, rep, s, d).sum(2)
    dv = dv_full[:, :, :s, :].reshape(b, kh, rep, s, d).sum(2)
    dk = jnp.transpose(dk, (0, 2, 1, 3)).astype(k.dtype)
    dv = jnp.transpose(dv, (0, 2, 1, 3)).astype(v.dtype)
    return dq, dk, dv, None, None


def _flash_fwd_rule(
    q, k, v, qseg, kseg, causal, interpret, soft_cap, window, block_sizes
):
    out, res = _flash_fwd_impl(
        q, k, v, qseg, kseg, causal, interpret, soft_cap, window,
        block_sizes=block_sizes,
    )
    return out, res


def _flash_bwd_rule(
    causal, interpret, soft_cap, window, block_sizes, res, g
):
    return _flash_bwd_impl(
        causal, interpret, soft_cap, window, res, g,
        block_sizes=block_sizes,
    )


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def default_interpret(platform: str) -> bool:
    """Pallas interpreter on CPU (tests, dryruns), Mosaic anywhere else."""
    interpret = platform == "cpu"
    announce_once(
        f"pallas flash kernels on platform={platform}: "
        + ("INTERPRETED (not Mosaic)" if interpret else "compiled by Mosaic")
    )
    return interpret


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids=None,
    kv_segment_ids=None,
    logits_soft_cap: float | None = None,
    sliding_window: int | None = None,
    interpret: bool | None = None,
    block_sizes: tuple[int | None, int | None] | None = None,
) -> jax.Array:
    """Flash attention. q:[B,T,H,D], k/v:[B,S,K,D] -> [B,T,H,D].

    ``segment_ids`` ([B, T] int) masks cross-segment attention for packed
    batches; ``kv_segment_ids`` ([B, S]) defaults to ``segment_ids`` (which
    then requires T == S, the self-attention training path).
    ``logits_soft_cap`` applies Gemma-style ``cap * tanh(logits/cap)`` to
    the scaled logits inside the kernel (fwd and both bwd kernels),
    matching ``xla_attention``'s semantics.

    ``interpret=None`` auto-selects the Pallas interpreter on CPU backends
    (tests, dryruns); any accelerator backend gets the real Mosaic lowering.

    ``block_sizes`` is an explicit (bq, bkv) grid-block override for the
    fwd and both bwd pallas kernels (either element None keeps that
    axis's heuristic); unset, the TPUFW_FLASH_BQ / TPUFW_FLASH_BKV env
    vars apply. Values must be 128 multiples dividing the padded
    lengths — validated with a clear error. Default behavior (no kwarg,
    no env) is unchanged.
    """
    h, kh = q.shape[2], k.shape[2]
    if h % kh:
        raise ValueError(f"q heads {h} not divisible by kv heads {kh}")
    qseg = segment_ids
    kseg = kv_segment_ids if kv_segment_ids is not None else segment_ids
    if (qseg is None) != (kseg is None):
        raise ValueError(
            "segment_ids and kv_segment_ids must be given together"
        )
    if qseg is not None and kv_segment_ids is None and (
        q.shape[1] != k.shape[1]
    ):
        raise ValueError(
            f"segment_ids without kv_segment_ids requires T==S "
            f"(self-attention); got T={q.shape[1]}, S={k.shape[1]}"
        )
    if interpret is None:
        interpret = default_interpret(jax.devices()[0].platform)
    cap = None if logits_soft_cap is None else float(logits_soft_cap)
    win = None if sliding_window is None else int(sliding_window)
    blocks = None if block_sizes is None else tuple(block_sizes)
    return _flash(q, k, v, qseg, kseg, causal, interpret, cap, win, blocks)
