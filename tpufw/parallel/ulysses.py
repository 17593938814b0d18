"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The second long-context strategy next to ring attention (SURVEY.md §5
names both: "ring attention / all-to-all"). Where the ring rotates K/V
chunks around neighbor ICI links and merges online-softmax statistics,
Ulysses does two ``lax.all_to_all`` transposes: sequence-sharded
projections [B, T/P, H, D] become head-sharded [B, T, H/P, D], each
device runs ordinary FULL-sequence attention over its head group (any
local backend — the Pallas flash kernel included — unchanged), and one
reverse all-to-all restores sequence sharding.

Trade-offs vs the ring (why tpufw ships both):
- Ulysses comm volume is O(T·H·D/P) per all-to-all, independent of the
  number of steps — two collectives total, no per-chunk latency chain;
  the ring pays P ppermute rounds but each is neighbor-only traffic.
- Ulysses parallelism is capped by head count (P must divide the local
  head count); the ring scales to any P that divides T.
- Ulysses reuses the exact single-device attention kernel (simpler
  numerics: no cross-chunk softmax merging).

GQA: if the kv-head count doesn't divide by P, kv heads are repeated up
to the query head count before the swap (costs bandwidth and, unlike
single-device ``xla_attention``, which contracts each kv head with its
group in place, materialises K and V at the query heads' width; exact
same math).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tpufw.mesh.mesh import AXIS_DATA, AXIS_FSDP, AXIS_SEQUENCE, AXIS_TENSOR
from tpufw.ops.attention import (
    _repeat_kv,
    announce_once,
    multi_head_attention,
)
from tpufw.parallel.context import current_mesh


def _ulysses_local(
    q, k, v, *seg, axis_name, causal, backend, soft_cap, window
):
    """Per-device body. q: [B, T/P, Hl, D], k/v: [B, T/P, Kl, D] local
    shapes (Hl = heads already divided by any tensor sharding outside).
    ``seg`` is () or (qseg [B, T/P],)."""
    n = jax.lax.psum(1, axis_name)
    h, kh = q.shape[2], k.shape[2]
    if h % n:
        raise ValueError(
            f"ulysses needs sequence-axis size {n} to divide the local "
            f"query head count {h}"
        )
    if kh % n:
        # GQA with too few kv heads for the swap: repeat up to H first.
        k = _repeat_kv(k, h // kh)
        v = _repeat_kv(v, h // kh)

    def swap(x):  # [B, T/P, H, D] -> [B, T, H/P, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    q_g, k_g, v_g = swap(q), swap(k), swap(v)
    seg_full = None
    if seg:
        # Every device needs the FULL-length segment ids for its heads.
        seg_full = jax.lax.all_gather(
            seg[0], axis_name, axis=1, tiled=True
        )

    out = multi_head_attention(
        q_g, k_g, v_g,
        causal=causal,
        segment_ids=seg_full,
        logits_soft_cap=soft_cap,
        sliding_window=window,
        backend=backend,
    )  # [B, T, H/P, D]
    # Reverse swap: back to [B, T/P, H, D].
    return jax.lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    axis_name: str = AXIS_SEQUENCE,
    backend: Optional[str] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Sequence-parallel attention via all-to-all. Global shapes
    q: [B,T,H,D], k/v: [B,S,K,D]; self-attention only (T == S), T must
    divide by the sequence-axis size, and H (after any tensor sharding)
    must divide by it too.

    ``backend`` is the LOCAL attention implementation each device runs on
    its head group ("xla" or "flash"); default picks flash on TPU for the
    causal path, xla elsewhere — mirroring ring_attention's choice.
    ``logits_soft_cap``/``sliding_window`` pass straight through to the
    local kernel: each device sees the FULL sequence for its heads, so
    Gemma-style capping and local attention need no extra handling here.
    """
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError(
            "ulysses_attention needs a mesh: pass mesh= or register one "
            "via tpufw.parallel.context.use_mesh(...)"
        )
    if q.shape[1] != k.shape[1]:
        raise ValueError(
            f"ulysses attention is self-attention only: T={q.shape[1]} "
            f"!= S={k.shape[1]}"
        )
    if backend is None:
        platform = mesh.devices.flatten()[0].platform
        backend = "flash" if (causal and platform == "tpu") else "xla"
        announce_once(
            f"ulysses attention on platform={platform}, causal={causal}: "
            f"local backend={backend!r}"
        )
    if backend not in ("xla", "flash"):
        raise ValueError(
            f"ulysses local backend must be 'xla' or 'flash', "
            f"got {backend!r}"
        )

    spec = P((AXIS_DATA, AXIS_FSDP), AXIS_SEQUENCE, AXIS_TENSOR, None)
    seg_spec = P((AXIS_DATA, AXIS_FSDP), AXIS_SEQUENCE)
    local = functools.partial(
        _ulysses_local,
        axis_name=axis_name,
        causal=causal,
        backend=backend,
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
        in_specs=(spec, spec, spec, seg_spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, segment_ids.astype(jnp.int32))
