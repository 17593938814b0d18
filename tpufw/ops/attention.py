"""Attention ops with switchable backends.

The reference has no compute ops at all (its workload is ``nvidia-smi``,
reference ``README.md:314``); attention exists here because BASELINE configs
3-5 are Llama/Mixtral training. Backends:

- ``"xla"``    — einsum softmax attention; a kv head is contracted with its
                 group of query heads as the cache stores it (decode steps,
                 long rows) or repeated to them where that is the cheaper
                 (many queries over a short row). Runs anywhere: CPU tests,
                 dryruns, the server's cached paths. The correctness
                 reference.
- ``"flash"``  — Pallas TPU flash-attention kernel (tpufw.ops.flash),
                 blockwise online-softmax in VMEM; long-seq memory O(T).
- ``"ring"``   — sequence-parallel ring attention over the ``sequence`` mesh
                 axis (tpufw.parallel.ring), for contexts longer than one
                 chip's HBM share.

All backends take [B, T, H, D] q and [B, S, K, D] k/v with K (kv heads)
dividing H (GQA: each kv head serves H//K query heads).
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@functools.lru_cache(maxsize=None)
def announce_once(choice: str) -> None:
    """Say once per process which implementation a platform-dependent
    default resolved to (Mosaic or the Pallas interpreter; the flash
    ring or its einsum reference), so a run's output shows whether the
    compiled side or the test side ran."""
    logging.getLogger("tpufw.attention").warning(choice)


def tanh_soft_cap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-style logit soft-capping: cap * tanh(x / cap). The ONE
    implementation — the xla backend, the Pallas flash kernels, the
    chunked-CE loss, and the Gemma head all call this, so the numerics
    cannot drift between them."""
    return cap * jnp.tanh(x / cap)


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, K, D] -> [B, S, K*n_rep, D] by repeating each kv head.

    For ``parallel/ring.py`` and ``parallel/ulysses.py``, whose per-shard
    math is written over query heads, and for ``xla_attention`` where
    ``_repeat_is_cheaper`` says so; everywhere else it contracts the group
    where it lies."""
    if n_rep == 1:
        return x
    b, s, k, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, k, n_rep, d))
    return x.reshape(b, s, k * n_rep, d)


#: The longest row over which XLA:TPU (v5e) computes max, exp and sum inside
#: the per-head logits dot. Past it the per-head form of a 512-query call
#: falls off a cliff: 72.8 ms at 8,192 keys where the grouped form takes 3.7
#: (PERF.md section 5, PR 36).
_FUSED_SOFTMAX_KEYS = 4096


def _repeat_is_cheaper(t: int, s: int, d: int) -> bool:
    """Whether a grouped-query call of ``t`` queries over ``s`` keys should
    give every query head its own copy of its kv head; from static shapes
    alone.

    XLA:TPU fuses the softmax of the per-head spelling into its two dots
    (the float32 logits are written once and read once) and reads the
    grouped spelling's logits three times, so where the logits outweigh
    the repeat the per-head form is ahead. Measured in the serving chunk
    programs (PERF.md section 5, PR 36): at 64 queries the grouped form is
    2% ahead, from 128 (a head's width) up the per-head form is 1-6% ahead,
    as long as the row is short enough for that fusion. So prefill chunks
    and training rows of up to 4,096 keys repeat K and V (in their own
    dtype, a few tens of MB); decode steps, verify blocks and every longer
    row contract the group in place, where a repeat costs 3-27x."""
    return t >= d and s <= _FUSED_SOFTMAX_KEYS


def attention_mask(
    t: int,
    s: int,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    q_positions: Optional[jax.Array] = None,
    sliding_window: Optional[int] = None,
    kv_positions: Optional[jax.Array] = None,
) -> Optional[jax.Array]:
    """Which of S keys each of T queries may attend: bool, broadcastable
    to [B, 1, T, S], or None when nothing is masked. The ONE predicate —
    ``xla_attention`` and MLA's absorbed decode (a different
    contraction over the same cache slots) both fill with it. Arguments
    as ``xla_attention``'s."""
    mask = None
    if kv_positions is None:
        kpos = jnp.arange(s)[None, None, None, :]  # [1,1,1,S]
    else:
        kpos = kv_positions[:, None, None, :]  # [B,1,1,S]
    if causal or sliding_window is not None:
        if q_positions is None:
            # Align query i with absolute position s-t+i.
            qpos = (jnp.arange(t) + (s - t))[None, None, :, None]
        else:
            qpos = q_positions[:, None, :, None]  # [B,1,T,1]
        if causal:
            mask = qpos >= kpos
        if sliding_window is not None:
            # Local attention (Gemma-style): only the last
            # ``sliding_window`` positions are visible.
            near = (qpos - kpos) < sliding_window
            mask = near if mask is None else (mask & near)
    if segment_ids is not None:
        kv_seg = kv_segment_ids if kv_segment_ids is not None else segment_ids
        seg_mask = (
            segment_ids[:, None, :, None] == kv_seg[:, None, None, :]
        )
        mask = seg_mask if mask is None else (mask & seg_mask)
    return mask


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    q_positions: Optional[jax.Array] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    kv_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference softmax attention. q:[B,T,H,D], k:[B,S,K,D], v:[B,S,K,Dv]
    -> [B,T,H,Dv].

    Grouped-query heads (G = H // K query heads a kv head) are contracted
    where the cache stores them: q is viewed as [B,T,K,G,D] and each kv
    head's keys and values meet its G x T queries in one dot, in the
    operands' own dtype with float32 accumulation. Nothing of [B,S,H,D]
    exists then, in any dtype: a decode step that repeated K and V spent
    most of its time writing them in float32. The queries are the dot's
    rows ([.., G, T, S] logits): XLA:TPU then runs mask, softmax and the
    value dot on the logits as the first dot left them, where with the
    keys as rows it copies them in float32 wherever G x T outnumbers S.
    MQA (K = 1) is the same code with a unit axis. Two cases keep the
    per-head spelling, one kv head a query head: MHA (G = 1), which has
    nothing to repeat, and ``_repeat_is_cheaper``'s many queries over a
    short row, where the repeat is small and the logits are what costs.

    ``segment_ids`` ([B, T] int) masks cross-segment attention for packed
    sequences; ``kv_segment_ids`` ([B, S]) gives the key side its own ids
    when q and kv lengths differ (KV-cache decode — cached pad slots carry
    segment 0 and are never attended). ``q_positions`` ([B or 1, T] int) are the
    queries' absolute positions in the S-long key axis for causal masking;
    default assumes queries are the final T positions. Softmax is computed
    in float32 regardless of input dtype — bf16 logits lose too much
    precision at long T. ``sliding_window`` masks keys more than that
    many positions behind the query (local attention). ``kv_positions``
    ([B, S] int) are the keys' own positions where they are not 0..S-1 in
    order (a ring of the last S keys: tpufw.ops.kv_store).
    """
    b, t, h, d = q.shape
    _, s, kh, _ = k.shape
    if h % kh:
        raise ValueError(f"q heads {h} not divisible by kv heads {kh}")
    g = h // kh
    if g == 1 or _repeat_is_cheaper(t, s, d):
        k, v = _repeat_kv(k, g), _repeat_kv(v, g)
        logits_of, values_of = "bthd,bshd->bhts", "bhts,bshd->bthd"
    else:
        q = q.reshape(b, t, kh, g, d)
        logits_of, values_of = "btkgd,bskd->bkgts", "bkgts,bskd->btkgd"

    scale = 1.0 / math.sqrt(d)
    # fp32 accumulation on the MXU: bf16 logits would already have lost the
    # precision the fp32 softmax is supposed to protect.
    logits = (
        jnp.einsum(logits_of, q, k, preferred_element_type=jnp.float32)
        * scale
    )
    if logits_soft_cap is not None:
        logits = tanh_soft_cap(logits, logits_soft_cap)

    mask = attention_mask(
        t, s, causal=causal, segment_ids=segment_ids,
        kv_segment_ids=kv_segment_ids, q_positions=q_positions,
        sliding_window=sliding_window, kv_positions=kv_positions,
    )
    if mask is not None:
        # [B or 1, 1, T, S] over the heads, which take one axis or two.
        logits = jnp.where(
            mask if logits.ndim == 4 else mask[:, :, None], logits, -1e30
        )

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum(values_of, probs, v)
    # V's head width, not q's: MLA's uncached path has them differ.
    return out.reshape(b, t, h, v.shape[-1])


def _flash_over_mesh(q, k, v, *, segment_ids, **kwargs) -> jax.Array:
    """The Pallas flash kernel, placed where Mosaic can compile it.

    Mosaic kernels cannot be partitioned by GSPMD: under a jit whose
    computation spans more than one device the call must sit inside a
    ``shard_map`` with EVERY mesh axis manual. The mesh is the one the
    trainer registers (``tpufw.parallel.context.use_mesh``) and the
    layout is the one ring/ulysses use — batch over (data, fsdp), heads
    over tensor; attention is independent per batch row and per
    kv-head group, so the per-shard kernel needs no collective. Axes
    the spec does not name (sequence, expert, pipe) see replicated
    operands. Without a registered mesh, on a one-device mesh, or when
    the caller is already inside a manual region (pipeline stages,
    ulysses), the kernel is called as is.
    """
    from tpufw.mesh.mesh import AXIS_DATA, AXIS_FSDP, AXIS_TENSOR
    from tpufw.ops.flash import flash_attention
    from tpufw.parallel.context import current_mesh

    kernel = functools.partial(flash_attention, **kwargs)
    mesh = current_mesh()
    if (
        mesh is None
        or mesh.size == 1
        or jax.sharding.get_abstract_mesh().manual_axes
    ):
        return kernel(q, k, v, segment_ids=segment_ids)
    dp = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
    tp = mesh.shape[AXIS_TENSOR]
    if q.shape[0] % dp or q.shape[2] % tp or k.shape[2] % tp:
        raise ValueError(
            f"flash attention over mesh {dict(mesh.shape)}: batch "
            f"{q.shape[0]} must divide over data x fsdp = {dp}, and q/kv "
            f"heads {q.shape[2]}/{k.shape[2]} over tensor = {tp}"
        )
    spec = P((AXIS_DATA, AXIS_FSDP), None, AXIS_TENSOR, None)
    seg = () if segment_ids is None else (segment_ids,)
    return jax.shard_map(
        lambda q, k, v, *seg: kernel(
            q, k, v, segment_ids=seg[0] if seg else None
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec)
        + (P((AXIS_DATA, AXIS_FSDP), None),) * len(seg),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, *seg)


def multi_head_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    q_positions: Optional[jax.Array] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    kv_positions: Optional[jax.Array] = None,
    backend: str = "xla",
) -> jax.Array:
    """Backend dispatcher — the single attention entry point for all models."""
    if backend == "xla":
        return xla_attention(
            q,
            k,
            v,
            causal=causal,
            segment_ids=segment_ids,
            kv_segment_ids=kv_segment_ids,
            q_positions=q_positions,
            logits_soft_cap=logits_soft_cap,
            sliding_window=sliding_window,
            kv_positions=kv_positions,
        )
    if (
        kv_segment_ids is not None
        or q_positions is not None
        or kv_positions is not None
    ):
        raise NotImplementedError(
            f"KV-cache decode (kv_segment_ids/q_positions) requires "
            f"backend='xla', got {backend!r}"
        )
    if backend == "flash":
        return _flash_over_mesh(
            q, k, v, causal=causal, segment_ids=segment_ids,
            logits_soft_cap=logits_soft_cap,
            sliding_window=sliding_window,
        )
    if backend == "ring":
        from tpufw.parallel.ring import ring_attention

        return ring_attention(
            q, k, v, causal=causal, segment_ids=segment_ids,
            logits_soft_cap=logits_soft_cap,
            sliding_window=sliding_window,
        )
    if backend == "ulysses":
        from tpufw.parallel.ulysses import ulysses_attention

        return ulysses_attention(
            q, k, v, causal=causal, segment_ids=segment_ids,
            logits_soft_cap=logits_soft_cap,
            sliding_window=sliding_window,
        )
    raise ValueError(f"unknown attention backend {backend!r}")
