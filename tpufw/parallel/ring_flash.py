"""Ring attention with the Pallas flash kernel per shard (VERDICT r1 item 5).

The einsum ring (tpufw.parallel.ring) holds one [B, H, L, L] logits block
per chunk step — fine as a reference, but it caps the per-device context at
whatever a materialized logits block allows, defeating the point of
sequence parallelism. Here each ring step runs the blockwise flash kernel
(tpufw.ops.flash) on the resident q shard against the visiting kv chunk, so
per-device memory is O(L·D) regardless of total context length:

  memory     einsum ring:  O(L²)  per device per step
             flash ring:   O(L)   (online softmax in VMEM)

Forward: chunks merge by their log-sum-exp — for normalized partial
outputs o₁, o₂ with lse₁, lse₂:  o = w₁o₁ + w₂o₂, wᵢ = exp(lseᵢ - lse₁₊₂).

Backward is the flash trick lifted to the ring: a custom VJP recomputes
per-chunk probabilities from (q, k_chunk, GLOBAL lse) — the same kernels
as single-device flash backward (tpufw.ops.flash._flash_bwd_impl), called
once per visiting chunk — while (k, v, dk_acc, dv_acc) rotate together
around the ring; after n rotations each chunk's gradient accumulator is
back on its owner with every device's contribution summed.

Causality at chunk granularity is a static 3-way case (the chunk-vs-chunk
position is data-dependent only through ``axis_index``): kv chunk entirely
before the q shard -> full attention; the diagonal chunk -> causal; after
-> no contribution. ``lax.switch`` selects between three compiled kernels.

Packed-batch ``segment_ids`` ride the ring with their kv chunk exactly as
in the einsum ring; the flash kernels mask cross-segment pairs in-block.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tpufw.mesh.mesh import AXIS_DATA, AXIS_FSDP, AXIS_SEQUENCE, AXIS_TENSOR
from tpufw.ops import flash as F
from tpufw.parallel.context import current_mesh

NEG_INF = F.NEG_INF


def _chunk_fwd(
    case, q, k, v, qseg, kseg, interpret, soft_cap=None, window=None,
    offset=0,
):
    """One q-shard x kv-chunk flash forward. Returns (o [B,L,H,D] fp32
    normalized, lse [B,H,L] fp32). case: 0 full / 1 causal-diag / 2 empty.

    ``offset`` is the STATIC global distance of the q shard ahead of the
    visiting kv chunk (step*L on the ring) — with a ``window`` it makes
    the in-kernel (q_pos - k_pos) < window mask see global positions.
    Only the "full" branch uses it (the diagonal branch is only
    reachable at step 0, offset 0); for offset >= L every pair is
    already causal, so causal=False there stays correct."""
    b, l, h, d = q.shape

    def run(causal):
        def f(q, k, v, qseg, kseg):
            out, res = F._flash_fwd_impl(
                q, k, v, qseg, kseg, causal, interpret, soft_cap,
                window, offset=(0 if causal else offset),
            )
            lse = res[-1][:, :, 0, :l]  # un-pad [B,H,1,Tp] -> [B,H,L]
            return out.astype(jnp.float32), lse

        return f

    def empty(q, k, v, qseg, kseg):
        return (
            jnp.zeros((b, l, h, d), jnp.float32),
            jnp.full((b, h, l), NEG_INF, jnp.float32),
        )

    return jax.lax.switch(
        case, (run(False), run(True), empty), q, k, v, qseg, kseg
    )


def _chunk_bwd(
    case, q, k, v, qseg, kseg, out, lse_pad, g, interpret, soft_cap=None,
    window=None, offset=0,
):
    """Per-chunk gradients via the flash backward kernels with the GLOBAL
    lse. Returns (dq, dk, dv) in fp32. ``window``/``offset`` as in
    ``_chunk_fwd``."""

    def run(causal):
        def f(q, k, v, qseg, kseg, out, lse_pad, g):
            dq, dk, dv, _, _ = F._flash_bwd_impl(
                causal, interpret, soft_cap, window,
                (q, k, v, qseg, kseg, out, lse_pad), g,
                offset=(0 if causal else offset),
            )
            return (
                dq.astype(jnp.float32),
                dk.astype(jnp.float32),
                dv.astype(jnp.float32),
            )

        return f

    def empty(q, k, v, qseg, kseg, out, lse_pad, g):
        return (
            jnp.zeros(q.shape, jnp.float32),
            jnp.zeros(k.shape, jnp.float32),
            jnp.zeros(v.shape, jnp.float32),
        )

    return jax.lax.switch(
        case, (run(False), run(True), empty),
        q, k, v, qseg, kseg, out, lse_pad, g,
    )


def _merge(out, lse, o_c, lse_c):
    """Merge normalized partials by log-sum-exp (docstring formula)."""
    lse_new = jnp.logaddexp(lse, lse_c)
    w1 = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(lse - lse_new))
    w2 = jnp.where(lse_c <= NEG_INF / 2, 0.0, jnp.exp(lse_c - lse_new))
    # [B,H,L] weights -> [B,L,H,1] to scale [B,L,H,D] outputs.
    t = lambda w: jnp.transpose(w, (0, 2, 1))[..., None]  # noqa: E731
    return t(w1) * out + t(w2) * o_c, lse_new


def _n_live_steps(n: int, l: int, window) -> int:
    """How many ring steps can contribute under a sliding window.

    At step s > 0 the visiting chunk sits exactly s*L positions behind
    the q shard, so the closest pair is (s-1)*L + 1 apart; once that
    reaches the window the chunk — and every later (farther) one — is
    statically invisible. This is where windowed ring attention's
    savings come from: ceil-bounded rotations instead of n (e.g. a 4k
    window over 8 x 8k shards runs 2 of 8 steps)."""
    if window is None:
        return n
    s = 1
    while s < n and (s - 1) * l + 1 < window:
        s += 1
    return s


def _make_local(
    n: int, axis_name: str, interpret: bool, has_seg: bool,
    soft_cap=None, window=None,
):
    """Build the per-device custom-VJP ring-flash body for a ring of n."""
    perm = [(i, (i + 1) % n) for i in range(n)]

    def case_of(src, idx):
        # 0 full (chunk before shard), 1 diag (causal), 2 empty (after).
        return jnp.int32(src == idx) + 2 * jnp.int32(src > idx)

    def fwd(q, k, v, qseg, kseg):
        idx = jax.lax.axis_index(axis_name)
        b, l, h, d = q.shape
        steps = _n_live_steps(n, l, window)
        out = jnp.zeros((b, l, h, d), jnp.float32)
        lse = jnp.full((b, h, l), NEG_INF, jnp.float32)
        k_cur, v_cur, kseg_cur = k, v, kseg
        for step in range(steps):  # unrolled: static mesh-axis size
            src = (idx - step) % n
            o_c, lse_c = _chunk_fwd(
                case_of(src, idx), q, k_cur, v_cur, qseg, kseg_cur,
                interpret, soft_cap, window, offset=step * l,
            )
            out, lse = _merge(out, lse, o_c, lse_c)
            if step < steps - 1:
                k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
                v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
                if has_seg:
                    kseg_cur = jax.lax.ppermute(kseg_cur, axis_name, perm)
        return out.astype(q.dtype), lse

    @jax.custom_vjp
    def local(q, k, v, qseg, kseg):
        return fwd(q, k, v, qseg, kseg)[0]

    def fwd_rule(q, k, v, qseg, kseg):
        out, lse = fwd(q, k, v, qseg, kseg)
        return out, (q, k, v, qseg, kseg, out, lse)

    def bwd_rule(res, g):
        q, k, v, qseg, kseg, out, lse = res
        idx = jax.lax.axis_index(axis_name)
        l = q.shape[1]
        steps = _n_live_steps(n, l, window)
        # The flash bwd kernels take lse in the padded [B,H,1,Tp] layout.
        l_pad = -l % 128
        lse_pad = jnp.pad(lse, ((0, 0), (0, 0), (0, l_pad)))[:, :, None, :]
        dq = jnp.zeros(q.shape, jnp.float32)
        k_cur, v_cur, kseg_cur = k, v, kseg
        dk_acc = jnp.zeros(k.shape, jnp.float32)
        dv_acc = jnp.zeros(v.shape, jnp.float32)
        for step in range(steps):
            src = (idx - step) % n
            dq_c, dk_c, dv_c = _chunk_bwd(
                case_of(src, idx), q, k_cur, v_cur, qseg, kseg_cur,
                out, lse_pad, g, interpret, soft_cap, window,
                offset=step * l,
            )
            dq = dq + dq_c
            dk_acc = dk_acc + dk_c
            dv_acc = dv_acc + dv_c
            # Rotate accumulators WITH their chunk every live step; the
            # final hop home happens below in ONE collective.
            if step < steps - 1:
                k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
                v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
                dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
                dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
                if has_seg:
                    kseg_cur = jax.lax.ppermute(kseg_cur, axis_name, perm)
        # After steps-1 rotations a chunk owned by device o sits on
        # device (o + steps - 1) % n: one ppermute of distance
        # n - (steps - 1) sends every accumulator home (with a full
        # window this is the same single +1 hop the old loop ended on).
        home = (n - (steps - 1)) % n
        if home:
            perm_home = [(i, (i + home) % n) for i in range(n)]
            dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm_home)
            dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm_home)
        return (
            dq.astype(q.dtype),
            dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype),
            None,
            None,
        )

    local.defvjp(fwd_rule, bwd_rule)
    return local


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    axis_name: str = AXIS_SEQUENCE,
    interpret: Optional[bool] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Sequence-parallel flash attention. Global shapes q:[B,T,H,D],
    k/v:[B,T,K,D]; sharded over (batch=data+fsdp, seq=sequence,
    heads=tensor) like the einsum ring. Causal only (the LM path): the
    chunk-level case analysis assumes it.

    ``sliding_window`` (Mistral/Gemma-local layers) runs in-kernel with
    GLOBAL positions — the per-step chunk distance is static on the
    unrolled ring, so the window needs no traced offsets — and cuts the
    ring short: chunks entirely beyond the window are never computed or
    rotated (``_n_live_steps``).
    """
    if not causal:
        raise NotImplementedError(
            "ring_flash_attention is causal-only; use the einsum ring "
            "(impl='einsum') for non-causal sequence parallelism"
        )
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError(
            "ring_flash_attention needs a mesh: pass mesh= or register one "
            "via tpufw.parallel.context.use_mesh(...)"
        )
    if q.shape[1] != k.shape[1]:
        raise ValueError(
            f"ring attention is self-attention only: T={q.shape[1]} != "
            f"S={k.shape[1]}"
        )
    n = mesh.shape[axis_name]
    if interpret is None:
        interpret = F.default_interpret(mesh.devices.flatten()[0].platform)
    has_seg = segment_ids is not None
    cap = None if logits_soft_cap is None else float(logits_soft_cap)
    win = None if sliding_window is None else int(sliding_window)
    if win is not None and win < 1:
        raise ValueError(f"sliding_window must be >= 1, got {win}")
    local = _make_local(n, axis_name, interpret, has_seg, cap, win)

    spec = P((AXIS_DATA, AXIS_FSDP), AXIS_SEQUENCE, AXIS_TENSOR, None)
    seg_spec = P((AXIS_DATA, AXIS_FSDP), AXIS_SEQUENCE)
    if has_seg:
        seg = segment_ids.astype(jnp.int32)
        fn = shard_map(
            lambda q, k, v, qs, ks: local(q, k, v, qs, ks),
            mesh=mesh,
            in_specs=(spec, spec, spec, seg_spec, seg_spec),
            out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v, seg, seg)
    fn = shard_map(
        lambda q, k, v: local(q, k, v, None, None),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
