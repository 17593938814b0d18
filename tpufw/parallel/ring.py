"""Ring attention: sequence/context parallelism over the ``sequence`` mesh axis.

Long-context design per SURVEY.md §5: activations are sharded along the
sequence dimension; K/V shards rotate around the ring via
``jax.lax.ppermute`` (XLA lowers it to ICI collective-permute) while each
device accumulates attention for its resident Q shard with online-softmax
merging — attention over a context n_seq times longer than one chip could
hold, with comms riding neighbor ICI links instead of all-gathers.

The global causal mask falls out of absolute positions: device d holds
positions [d*L, (d+1)*L); masks compare global q/k positions, so the
same SPMD code handles the full/partial/empty chunk cases.
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
from tpufw.ops.attention import _repeat_kv, announce_once, tanh_soft_cap
from tpufw.parallel.context import current_mesh

NEG_INF = -1e30


def _chunk_attn(
    q, k, v, q_start, k_start, causal, scale, rep, qseg=None, kseg=None,
    soft_cap=None, window=None,
):
    """Attention of local q against one kv chunk; returns (acc, m, l) stats.

    q: [B,T,H,D], k/v: [B,S,K,D] with H = K*rep (GQA repeat happens here,
    post-ppermute, so the ring never rotates repeated bytes).
    qseg [B,T] / kseg [B,S]: packed-batch segment ids; the key-side ids
    rotate around the ring with their kv chunk.
    m/l: [B,H,T,1] running max / normalizer in fp32.
    """
    k = _repeat_kv(k, rep)
    v = _repeat_kv(v, rep)
    logits = (
        jnp.einsum(
            "bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32
        )
        * scale
    )
    if soft_cap is not None:
        # Position-independent and elementwise: capping per chunk before
        # the online-softmax merge equals capping the full logits.
        logits = tanh_soft_cap(logits, soft_cap)
    mask = None
    if causal or window is not None:
        t, s = q.shape[1], k.shape[1]
        q_pos = q_start + jnp.arange(t)[:, None]
        k_pos = k_start + jnp.arange(s)[None, :]
        if causal:
            mask = (q_pos >= k_pos)[None, None]
        if window is not None:
            near = ((q_pos - k_pos) < window)[None, None]
            mask = near if mask is None else (mask & near)
    if qseg is not None:
        seg_mask = qseg[:, None, :, None] == kseg[:, None, None, :]
        mask = seg_mask if mask is None else (mask & seg_mask)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)  # [B,H,T,1]
    p = jnp.exp(logits - m)
    # Guard fully-masked chunks: exp(NEG_INF - NEG_INF) would be 1.
    p = jnp.where(m <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhts,bshd->bhtd", p.astype(q.dtype), v).astype(
        jnp.float32
    )
    return acc, m, l


def _ring_attn_local(
    q, k, v, *seg, causal, axis_name, scale, rep, soft_cap, window
):
    """Body run per-device under shard_map. q: [B,L,H,D], k/v: [B,L,K,D].
    ``seg`` is () or (qseg [B,L], kseg [B,L]); kseg rides the ring with kv."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    b, _, h, d = q.shape
    qseg, kseg0 = seg if seg else (None, None)

    m0 = jnp.full((b, h, t_local, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    # kseg rotates with its kv chunk. ``seg`` is a static (Python-level)
    # choice, so the unsegmented trace carries no dummy array and issues no
    # extra ppermute.
    has_seg = qseg is not None

    def body(step, carry):
        if has_seg:
            k_cur, v_cur, kseg_cur, m, l, acc = carry
        else:
            k_cur, v_cur, m, l, acc = carry
            kseg_cur = None
        src_chunk = (idx - step) % n
        acc_c, m_c, l_c = _chunk_attn(
            q,
            k_cur,
            v_cur,
            q_start=idx * t_local,
            k_start=src_chunk * t_local,
            causal=causal,
            scale=scale,
            rep=rep,
            qseg=qseg,
            kseg=kseg_cur,
            soft_cap=soft_cap,
            window=window,
        )
        m_new = jnp.maximum(m, m_c)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        beta = jnp.where(m_c <= NEG_INF / 2, 0.0, jnp.exp(m_c - m_new))
        l_new = l * alpha + l_c * beta
        acc_new = acc * alpha + acc_c * beta
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        if has_seg:
            kseg_nxt = jax.lax.ppermute(kseg_cur, axis_name, perm)
            return k_nxt, v_nxt, kseg_nxt, m_new, l_new, acc_new
        return k_nxt, v_nxt, m_new, l_new, acc_new

    init = (k, v, kseg0, m0, l0, acc0) if has_seg else (k, v, m0, l0, acc0)
    out_carry = jax.lax.fori_loop(0, n, body, init)
    m, l, acc = out_carry[-3], out_carry[-2], out_carry[-1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe).astype(q.dtype)  # [B,H,T,D]
    return jnp.transpose(out, (0, 2, 1, 3))


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    axis_name: str = AXIS_SEQUENCE,
    impl: Optional[str] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Sequence-parallel attention. q:[B,T,H,D], k/v:[B,S,K,D] global shapes.

    Wraps its own ``shard_map`` over (batch=data+fsdp, seq=sequence,
    heads=tensor); requires a registered current mesh (tpufw.parallel.context)
    or an explicit ``mesh``. T must equal S (self-attention) and divide
    evenly by the sequence-axis size. ``segment_ids`` ([B, T] int) masks
    cross-segment attention for packed batches; the key-side copy rotates
    around the ring with its kv chunk.

    ``impl``: "flash" = Pallas flash kernel per shard (O(L) memory,
    tpufw.parallel.ring_flash — the long-context scaling path); "einsum" =
    materialized per-chunk logits (the reference implementation). Default
    (None) picks flash on TPU for the causal LM path and einsum elsewhere;
    the two are numerically interchangeable (tests/test_ring_flash.py).

    ``logits_soft_cap`` (Gemma) works on both impls (elementwise, so
    per-chunk capping commutes with the online-softmax merge).
    ``sliding_window`` (Mistral/Gemma-local) works on both impls too:
    the flash path passes the ring step's STATIC chunk distance as the
    kernel's position offset, so window masks see global positions, and
    chunks entirely beyond the window skip compute and rotation — a
    window spanning w shards runs ~w of n ring steps.
    """
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError(
            "ring_attention needs a mesh: pass mesh= or register one via "
            "tpufw.parallel.context.use_mesh(...)"
        )
    if sliding_window is not None and sliding_window < 1:
        # Checked here so BOTH impls fail loudly: window=0 would mask
        # every logit (einsum would silently emit uniform-softmax means).
        raise ValueError(
            f"sliding_window must be >= 1, got {sliding_window}"
        )
    if impl is None:
        platform = mesh.devices.flatten()[0].platform
        impl = "flash" if (causal and platform == "tpu") else "einsum"
        announce_once(
            f"ring attention on platform={platform}, causal={causal}: "
            f"impl={impl!r}"
        )
    if impl == "flash":
        # sliding_window runs in-kernel: the per-step chunk distance is
        # static on the unrolled ring, so window masks see global
        # positions without traced offsets, and out-of-window chunks
        # skip compute AND rotation (tpufw.parallel.ring_flash).
        from tpufw.parallel.ring_flash import ring_flash_attention

        return ring_flash_attention(
            q, k, v,
            causal=causal,
            segment_ids=segment_ids,
            mesh=mesh,
            axis_name=axis_name,
            logits_soft_cap=logits_soft_cap,
            sliding_window=sliding_window,
        )
    if impl != "einsum":
        raise ValueError(f"unknown ring impl {impl!r}")
    if q.shape[1] != k.shape[1]:
        raise ValueError(
            f"ring attention is self-attention only: T={q.shape[1]} != "
            f"S={k.shape[1]}"
        )
    rep = q.shape[2] // k.shape[2]
    spec = P((AXIS_DATA, AXIS_FSDP), AXIS_SEQUENCE, AXIS_TENSOR, None)
    seg_spec = P((AXIS_DATA, AXIS_FSDP), AXIS_SEQUENCE)
    scale = 1.0 / math.sqrt(q.shape[-1])
    local = functools.partial(
        _ring_attn_local,
        causal=causal,
        axis_name=axis_name,
        scale=scale,
        rep=rep,
        soft_cap=logits_soft_cap,
        window=sliding_window,
    )
    if segment_ids is None:
        fn = shard_map(
            local,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec, seg_spec, seg_spec),
        out_specs=spec,
        check_vma=False,
    )
    seg = segment_ids.astype(jnp.int32)
    return fn(q, k, v, seg, seg)
