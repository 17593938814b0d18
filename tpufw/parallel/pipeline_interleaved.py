"""Interleaved virtual-stage 1F1B: bubble / v, activations O(v*S).

Vanilla 1F1B (``pipeline_1f1b``) gives each device ONE contiguous
stage, so the pipeline fills and drains in S-1 ticks — the bubble
fraction (S-1)/(M+S-1) is fixed by the device count. This module
implements the Megatron-LM interleaved schedule instead: each device
owns ``v`` NON-contiguous virtual stages ("chunks"), a chunk being
1/v-th of the old stage's layers, so a tick's unit of work shrinks by
v while the fill still takes S-1 (now v-times-smaller) ticks — the
bubble drops to (S-1)/(v*M+S-1) at the cost of v-times more handoffs
per microbatch. Activation stash is a ring of 2*v*S chunk inputs:
O(v*S), independent of M, same bound as the 1F1B ring times v.

Layout: the stage stack is ``[v, S, layers_per_chunk, ...]`` with the
pipe axis on dim 1 (``stage_partition_specs(virtual=True)``); chunk
c = k*S + d holds layers [c*lpc, (c+1)*lpc) and lives on device
d = c mod S — the round-robin assignment that makes the wrap-around
dependency (chunk k on device 0 needs chunk k-1 from device S-1) line
up in lockstep.

Schedule algebra (S stages, v chunks/device, M microbatches with
M % S == 0, G = M/S groups; microbatch j = g*S + r):
  - FORWARD of chunk k, mb (g, r) on device d at tick
      t = d + g*v*S + k*S + r
    i.e. device d's forward sub-ticks are the contiguous window
    [d, d + v*M) and the offset tau = t - d decomposes uniquely as
    g*(v*S) + k*S + r — groups outermost, then chunks, then the S
    microbatches of the group.
  - BACKWARD of chunk k, mb (g, r) on device d at tick
      t = (v*S - 1) + (S-1-d) + g*v*S + (v-1-k)*S + r
    (mirror order: last chunk first). The LAST chunk's forward and
    backward of a microbatch land on device S-1 at the SAME tick, so
    the in-region loss epilogue feeds the cotangent ring directly,
    exactly like 1F1B.
  - total ticks T = v*M + (v+1)*S - 2 (equals 1F1B's M + 2S - 2 at
    v = 1); each device is forward-busy v*M contiguous ticks inside a
    global span of v*M + S - 1, which is the (S-1)/(v*M+S-1) bubble
    accounting pinned by tests.
  - handoffs are the SAME two ppermutes per tick as 1F1B (fwd to d+1,
    cotangent to d-1, consumed next tick), issued early so they
    overlap the tick's compute — v times MORE total handoffs per
    microbatch, each 1x activation size, is the price of the smaller
    bubble (PERF.md quantifies when it pays).
  - a stash written at offset tau_f is read when its chunk's backward
    comes up; lifetime <= 2*v*S - 2 ticks, so ``tau_f mod 2*v*S``
    slots never collide.

Gradient exactness: same manual-VJP discipline as 1F1B (full remat of
the chunk forward from the stash, Megatron f/g custom collectives for
tensor parallelism, masked accumulation + one epilogue reduction).
Parity with the GPipe autodiff path is pinned by
tests/test_pipeline_interleaved.py at the tests/test_pipeline_1f1b.py
tolerance.

Scope: Llama-family dense blocks (incl. Qwen qkv biases), data/fsdp x
tensor composition — the ``_check_1f1b`` envelope. Requires
M % S == 0 and n_layers % (v*S) == 0 (``PipelineConfig.validate``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from tpufw.mesh import AXIS_DATA, AXIS_FSDP, AXIS_PIPE, AXIS_TENSOR
from tpufw.models.llama import LlamaConfig
from tpufw.parallel.pipeline import (
    PipelineConfig,
    stage_partition_specs,
)
from tpufw.parallel.pipeline_1f1b import (
    _VOCAB_REDUCE_AXES,
    _check_1f1b,
    _embed_fwd,
    _epilogue_loss,
    _stage_1f1b,
    vocab_scatter_plan,
)

#: Trace-time counters (bumped when the chunk forward is TRACED, not
#: when it runs). tests/test_pipeline_interleaved.py pins that a
#: compile traces the chunk body O(1) times regardless of M — the
#: schedule lives in scan indices, not in unrolled Python.
TRACE_COUNTS = {"chunk_fwd": 0}


def _interleaved_local(
    stage_params,
    head_leaves,
    x_mb,
    tok_mb,
    tgt_mb,
    mask_mb,
    *seg_mb,
    cfg,
    backend,
    n_microbatches,
    n_virtual,
    loss_chunk_size,
    loss_chunk_dtype,
    vocab_scatter=False,
):
    """Per-device schedule body (inside shard_map). Mirrors
    ``_1f1b_local`` with the tick maps generalized to v chunks; see
    the module docstring for the algebra."""
    s = axis_size(AXIS_PIPE)
    sidx = jax.lax.axis_index(AXIS_PIPE)
    tp = axis_size(AXIS_TENSOR) > 1
    # [v, 1, lpc, ...] local shard -> [v, lpc, ...]
    stage_params = jax.tree.map(lambda a: a[:, 0], stage_params)
    m = n_microbatches
    v = n_virtual
    d_model = x_mb.shape[-1]
    mb_shape = x_mb.shape[1:]  # [mb, T, D]
    fwd_perm = [(i, (i + 1) % s) for i in range(s)]
    bwd_perm = [(i, (i - 1) % s) for i in range(s)]
    has_seg = bool(seg_mb)
    seg_all = seg_mb[0] if has_seg else None
    n_slots = 2 * v * s
    vm = v * m

    def chunk_fwd(p, x, seg):
        TRACE_COUNTS["chunk_fwd"] += 1
        return _stage_1f1b(p, x, cfg, backend, seg, tp)

    def pick(tree, k):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, k, 0, keepdims=False
            ),
            tree,
        )

    vocab = head_leaves["head"].shape[-1]

    def tick(carry, t):
        (
            f_recv, dx_prev, stash, loss_sum,
            g_stage, g_embed, g_fnorm, g_head,
        ) = carry
        # ---- tick -> (group, chunk, rank-in-group) maps -----------
        tau_f = t - sidx
        f_on = (tau_f >= 0) & (tau_f < vm)
        tau_fc = jnp.clip(tau_f, 0, vm - 1)
        kf = (tau_fc % (v * s)) // s
        jf = (tau_fc // (v * s)) * s + tau_fc % s  # g*S + r
        tau_b = t - (v * s - 1) - (s - 1 - sidx)
        b_on = (tau_b >= 0) & (tau_b < vm)
        tau_bc = jnp.clip(tau_b, 0, vm - 1)
        kb = (v - 1) - (tau_bc % (v * s)) // s
        gb = tau_bc // (v * s)
        rb = tau_bc % s
        jb = gb * s + rb

        # Cotangent handoff issued first — overlaps the forward math.
        b_recv = jax.lax.ppermute(dx_prev, AXIS_PIPE, bwd_perm)

        # ---- forward sub-tick (chunk kf, microbatch jf) -----------
        x_in = jnp.where(
            (sidx == 0) & (kf == 0), x_mb[jf], f_recv
        )
        seg_f = seg_all[jf] if has_seg else None
        y = chunk_fwd(pick(stage_params, kf), x_in, seg_f)
        f_send = jax.lax.ppermute(y, AXIS_PIPE, fwd_perm)
        # Stash ring write (guarded like 1F1B: clipped inactive ticks
        # must not clobber a live slot).
        slot_f = tau_fc % n_slots
        old_slot = jax.lax.dynamic_index_in_dim(
            stash, slot_f, 0, keepdims=False
        )
        stash = jax.lax.dynamic_update_index_in_dim(
            stash, jnp.where(f_on, x_in, old_slot), slot_f, 0
        )

        # Loss epilogue: only the LAST chunk on the LAST device ends
        # the model; same lax.cond economics as 1F1B.
        def head_loss(hl, hidden):
            return _epilogue_loss(
                hl, hidden, tgt_mb[jf], mask_mb[jf], cfg,
                loss_chunk_size, loss_chunk_dtype,
            )

        is_last = sidx == s - 1
        take_loss = is_last & (kf == v - 1) & f_on

        def run_epilogue(hl, hidden):
            return jax.value_and_grad(head_loss, argnums=(0, 1))(
                hl, hidden
            )

        def skip_epilogue(hl, hidden):
            return (
                jnp.zeros((), jnp.float32),
                (
                    jax.tree.map(jnp.zeros_like, hl),
                    jnp.zeros_like(hidden),
                ),
            )

        loss_j, (g_hl_j, dy_j) = jax.lax.cond(
            take_loss, run_epilogue, skip_epilogue, head_leaves, y
        )
        loss_sum = loss_sum + loss_j
        g_fnorm = g_fnorm + g_hl_j["final_norm"]
        g_head = g_head + g_hl_j["head"]

        # ---- backward sub-tick (chunk kb, microbatch jb) ----------
        # The last chunk's backward on the last device consumes ITS
        # OWN same-tick loss cotangent; everything else the ring.
        g_in = jnp.where(
            is_last & (kb == v - 1), dy_j.astype(x_in.dtype), b_recv
        )
        # Stash read: the slot the matching forward wrote, i.e. the
        # forward offset of (gb, kb, rb).
        slot_b = (gb * v * s + kb * s + rb) % n_slots
        x_stash = jax.lax.dynamic_index_in_dim(
            stash, slot_b, 0, keepdims=False
        )
        seg_b = seg_all[jb] if has_seg else None
        params_b = pick(stage_params, kb)
        _, chunk_vjp = jax.vjp(
            lambda p, x: chunk_fwd(p, x, seg_b), params_b, x_stash
        )
        dp_j, dx_j = chunk_vjp(g_in)
        # Masked accumulate into the chunk row of the [v, ...] grads.
        g_stage = jax.tree.map(
            lambda acc, g: jax.lax.dynamic_update_index_in_dim(
                acc,
                jax.lax.dynamic_index_in_dim(
                    acc, kb, 0, keepdims=False
                )
                + jnp.where(b_on, g, 0.0),
                kb,
                0,
            ),
            g_stage,
            dp_j,
        )
        # Chunk 0 on device 0 backprops into the embedding lookup.
        g_embed = g_embed.at[tok_mb[jb]].add(
            jnp.where(
                (sidx == 0) & (kb == 0) & b_on, dx_j, 0.0
            ).astype(g_embed.dtype)
        )

        return (
            f_send, dx_j, stash, loss_sum,
            g_stage, g_embed, g_fnorm, g_head,
        ), None

    zeros_mb = jnp.zeros(mb_shape, x_mb.dtype)
    init = (
        zeros_mb,
        zeros_mb,
        jnp.zeros((n_slots, *mb_shape), x_mb.dtype),
        jnp.zeros((), jnp.float32),
        jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), stage_params
        ),
        jnp.zeros((vocab, d_model), jnp.float32),
        jnp.zeros(head_leaves["final_norm"].shape, jnp.float32),
        jnp.zeros(head_leaves["head"].shape, jnp.float32),
    )
    n_ticks = vm + (v + 1) * s - 2
    (
        _, _, _, loss_sum, g_stage, g_embed, g_fnorm, g_head
    ), _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))

    # Same epilogue reductions as 1F1B (see its comments).
    batch_axes = (AXIS_DATA, AXIS_FSDP)
    loss_sum = jax.lax.psum(loss_sum, (AXIS_PIPE, *batch_axes))
    g_fnorm = jax.lax.psum(g_fnorm, (AXIS_PIPE, *batch_axes))
    if vocab_scatter:
        g_embed = jax.lax.psum_scatter(
            g_embed, _VOCAB_REDUCE_AXES, scatter_dimension=0,
            tiled=True,
        )
        g_head = jax.lax.psum_scatter(
            g_head, _VOCAB_REDUCE_AXES, scatter_dimension=1,
            tiled=True,
        )
    else:
        g_embed = jax.lax.psum(g_embed, _VOCAB_REDUCE_AXES)
        g_head = jax.lax.psum(g_head, _VOCAB_REDUCE_AXES)
    g_stage = jax.tree.map(
        lambda g: jax.lax.psum(g, batch_axes), g_stage
    )
    # Re-add the pipe axis the in_spec stripped: [v, ...] -> [v, 1, ...].
    g_stage = jax.tree.map(lambda g: g[:, None], g_stage)
    return loss_sum, g_stage, g_embed, g_fnorm, g_head


def pipeline_interleaved_value_and_grad(
    params: dict,
    batch: dict | jax.Array,
    cfg: LlamaConfig,
    pipe: PipelineConfig,
    mesh: Mesh,
    backend: Optional[str] = None,
    loss_chunk_size: Optional[int] = None,
    loss_chunk_dtype=None,
) -> tuple[jax.Array, dict]:
    """(mean token loss, grads) through the interleaved schedule —
    drop-in counterpart of ``pipeline_1f1b_value_and_grad`` for params
    in the ``[v, S, ...]`` virtual layout."""
    from tpufw.train.trainer import shift_and_mask

    _check_1f1b(cfg, mesh)
    if not pipe.virtual_layout:
        raise ValueError(
            f"schedule='{pipe.schedule}' is not the interleaved "
            "schedule; use pipeline_1f1b / GPipe entry points"
        )
    if mesh.shape[AXIS_PIPE] != pipe.n_stages:
        raise ValueError(
            f"PipelineConfig.n_stages={pipe.n_stages} but mesh pipe "
            f"axis has size {mesh.shape[AXIS_PIPE]}"
        )
    if not isinstance(batch, dict):
        batch = {"tokens": batch}
    inputs, targets, seg_in, mask = shift_and_mask(batch)
    pipe.validate(cfg, inputs.shape[0])
    backend = backend or cfg.attention_backend
    b, t = inputs.shape
    m = pipe.n_microbatches
    dp = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
    if (b // m) % dp:
        raise ValueError(
            f"microbatch rows {b // m} not divisible over "
            f"data x fsdp = {dp} devices"
        )
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)

    x = _embed_fwd(params["embed"], inputs, cfg.dtype)
    mbd = lambda a: a.reshape(m, b // m, *a.shape[1:])  # noqa: E731
    head_leaves = {
        "final_norm": params["final_norm"],
        "head": params["head"],
    }

    row = (AXIS_DATA, AXIS_FSDP)
    mb4 = P(None, row, None, None)
    mb3 = P(None, row, None)
    stage_specs = stage_partition_specs(
        params["stages"], virtual=True
    )
    hl_specs = {"final_norm": P(), "head": P()}
    scatter, embed_spec, head_spec = vocab_scatter_plan(
        params["head"].shape[-1], mesh
    )
    local = partial(
        _interleaved_local,
        cfg=cfg,
        backend=backend,
        n_microbatches=m,
        n_virtual=pipe.n_virtual,
        loss_chunk_size=loss_chunk_size,
        loss_chunk_dtype=loss_chunk_dtype,
        vocab_scatter=scatter,
    )
    args = [
        params["stages"], head_leaves, mbd(x), mbd(inputs),
        mbd(targets), mbd(mask.astype(jnp.float32)),
    ]
    in_specs = [stage_specs, hl_specs, mb4, mb3, mb3, mb3]
    if seg_in is not None:
        args.append(mbd(seg_in.astype(jnp.int32)))
        in_specs.append(mb3)
    loss_sum, g_stage, g_embed, g_fnorm, g_head = shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), stage_specs, embed_spec, P(), head_spec),
        check_vma=False,
    )(*args)

    n_tok = jnp.maximum(mask.sum(), 1.0)
    inv = (1.0 / n_tok).astype(jnp.float32)
    grads = {
        "embed": (g_embed * inv).astype(params["embed"].dtype),
        "stages": jax.tree.map(
            lambda g, p: (g * inv).astype(p.dtype),
            g_stage,
            params["stages"],
        ),
        "final_norm": (g_fnorm * inv).astype(
            params["final_norm"].dtype
        ),
        "head": (g_head * inv).astype(params["head"].dtype),
    }
    return loss_sum / n_tok, grads
