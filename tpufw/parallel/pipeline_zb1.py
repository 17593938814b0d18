"""ZB-H1-style zero-bubble 1F1B: backward split into B and W phases.

1F1B's drain bubble exists because a stage's backward is one monolith:
stage s can't start microbatch j's backward until the cotangent
arrives, and while it waits it has NOTHING else to do. The zero-bubble
observation (PAPERS.md, "zero bubble" line of work; this is the H1
variant) is that only the INPUT-gradient half (B) of the backward is
on the critical path — the WEIGHT-gradient half (W) has no consumer
until the optimizer step, so it can be deferred into the ticks that
used to be bubble. Each schedule tick here runs three sub-ticks:

  F: forward of microbatch  jf = t - s            (stash input)
  B: input-grad of          jb = t - 2(S-1) + s   (dx -> ring, NOW)
  W: weight-grad of         jw = t - 3(S-1) + 2s  (local accumulate)

W for microbatch j on stage s runs S-1-s ticks AFTER its B — stage
S-1 runs them back-to-back (delay 0), stage 0 defers the longest —
which is exactly the deferral that fills stage 0's drain bubble with
useful weight-grad work. Weight-grad accumulation is purely local
(same masked-accumulator + epilogue reductions as 1F1B), so the
schedule adds ZERO communication: the same two ppermutes per tick,
issued with the same compute-overlap placement as ``pipeline_1f1b``.

Bookkeeping (S stages, M microbatches, ticks t = 0 .. M+3(S-1)-1):
  - activation stash: written at t = j+s, read by B at j+2(S-1)-s and
    again by W at j+3(S-1)-2s — lifetime <= 3(S-1), ring of 3S slots.
  - cotangent stash: B stores the OUTPUT cotangent it consumed so W
    can transpose the same stage against it; read S-1-s ticks later,
    ring of S slots (stage S-1 writes and reads the same slot within
    one tick; sub-tick order B-then-W makes that well-defined).
  - the last stage's F and B of a microbatch share a tick (in-region
    loss epilogue feeds B directly), as in 1F1B.
  - analytic bubble: per-device busy sub-slots 3M in the
    (S-1)/(3M+S-1) accounting pinned by tests — at most the
    interleaved schedule's (S-1)/(vM+S-1) for any v <= 3.

The honest trade on this full-remat substrate: B re-runs the stage
forward to get its VJP (the same remat 1F1B does), and W re-runs it
AGAIN — ``jax.vjp`` residuals can't ride the scan carry across ticks,
so splitting the transpose costs one extra forward recompute per
microbatch per stage (~25% more stage FLOPs at bwd ~ 2x fwd). zb1
buys its bubble shape with compute; interleaved buys it with
handoffs. PERF.md has the selection guidance.

Gradient exactness: identical discipline to ``pipeline_1f1b`` (the
split transpose computes the same two VJP factors, just on different
ticks); parity with GPipe+autodiff is pinned by
tests/test_pipeline_interleaved.py at the same tolerance. Scope:
``_check_1f1b`` envelope (Llama-family dense incl. Qwen biases,
data/fsdp x tensor), canonical ``[S, lps, ...]`` stage layout.
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


def _zb1_local(
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
    loss_chunk_size,
    loss_chunk_dtype,
    vocab_scatter=False,
):
    """Per-device schedule body (inside shard_map); see module
    docstring for the three-phase tick algebra."""
    s = axis_size(AXIS_PIPE)
    sidx = jax.lax.axis_index(AXIS_PIPE)
    tp = axis_size(AXIS_TENSOR) > 1
    stage_params = jax.tree.map(lambda a: a[0], stage_params)
    m = n_microbatches
    d_model = x_mb.shape[-1]
    mb_shape = x_mb.shape[1:]
    fwd_perm = [(i, (i + 1) % s) for i in range(s)]
    bwd_perm = [(i, (i - 1) % s) for i in range(s)]
    has_seg = bool(seg_mb)
    seg_all = seg_mb[0] if has_seg else None
    n_slots = 3 * s  # activation ring (two readers, see docstring)

    def stage_fwd(p, x, seg):
        return _stage_1f1b(p, x, cfg, backend, seg, tp)

    vocab = head_leaves["head"].shape[-1]

    def tick(carry, t):
        (
            f_recv, dx_prev, stash, cot, loss_sum,
            g_stage, g_embed, g_fnorm, g_head,
        ) = carry
        jf = t - sidx                    # F microbatch
        jb = t - 2 * (s - 1) + sidx      # B microbatch
        jw = t - 3 * (s - 1) + 2 * sidx  # W microbatch
        f_on = (jf >= 0) & (jf < m)
        b_on = (jb >= 0) & (jb < m)
        w_on = (jw >= 0) & (jw < m)
        jf_c = jnp.clip(jf, 0, m - 1)
        jb_c = jnp.clip(jb, 0, m - 1)
        jw_c = jnp.clip(jw, 0, m - 1)

        # Cotangent handoff issued first — overlaps the F sub-tick.
        b_recv = jax.lax.ppermute(dx_prev, AXIS_PIPE, bwd_perm)

        # ---- F sub-tick -------------------------------------------
        x_in = jnp.where(sidx == 0, x_mb[jf_c], f_recv)
        seg_f = seg_all[jf_c] if has_seg else None
        y = stage_fwd(stage_params, x_in, seg_f)
        f_send = jax.lax.ppermute(y, AXIS_PIPE, fwd_perm)
        slot_f = jf_c % n_slots
        old_slot = jax.lax.dynamic_index_in_dim(
            stash, slot_f, 0, keepdims=False
        )
        stash = jax.lax.dynamic_update_index_in_dim(
            stash, jnp.where(f_on, x_in, old_slot), slot_f, 0
        )

        def head_loss(hl, hidden):
            return _epilogue_loss(
                hl, hidden, tgt_mb[jf_c], mask_mb[jf_c], cfg,
                loss_chunk_size, loss_chunk_dtype,
            )

        is_last = sidx == s - 1
        take_loss = is_last & f_on

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

        # ---- B sub-tick: input gradient only ----------------------
        g_in = jnp.where(is_last, dy_j.astype(x_in.dtype), b_recv)
        x_b = jax.lax.dynamic_index_in_dim(
            stash, jb_c % n_slots, 0, keepdims=False
        )
        seg_b = seg_all[jb_c] if has_seg else None
        _, vjp_x = jax.vjp(
            lambda xx: stage_fwd(stage_params, xx, seg_b), x_b
        )
        (dx_j,) = vjp_x(g_in)
        # Park the consumed output cotangent for this stage's W phase
        # (write-guarded: drain ticks clip jb onto a LIVE slot).
        slot_cb = jb_c % s
        old_cot = jax.lax.dynamic_index_in_dim(
            cot, slot_cb, 0, keepdims=False
        )
        cot = jax.lax.dynamic_update_index_in_dim(
            cot, jnp.where(b_on, g_in, old_cot), slot_cb, 0
        )
        g_embed = g_embed.at[tok_mb[jb_c]].add(
            jnp.where((sidx == 0) & b_on, dx_j, 0.0).astype(
                g_embed.dtype
            )
        )

        # ---- W sub-tick: weight gradient, deferred ----------------
        # Runs S-1-s ticks after the matching B — the deferral that
        # fills the drain bubble. Second forward recompute (see
        # docstring for why the VJP can't be split across ticks).
        x_w = jax.lax.dynamic_index_in_dim(
            stash, jw_c % n_slots, 0, keepdims=False
        )
        g_w = jax.lax.dynamic_index_in_dim(
            cot, jw_c % s, 0, keepdims=False
        )
        seg_w = seg_all[jw_c] if has_seg else None
        _, vjp_p = jax.vjp(
            lambda pp: stage_fwd(pp, x_w, seg_w), stage_params
        )
        (dp_j,) = vjp_p(g_w)
        g_stage = jax.tree.map(
            lambda acc, g: acc + jnp.where(w_on, g, 0.0),
            g_stage, dp_j,
        )

        return (
            f_send, dx_j, stash, cot, loss_sum,
            g_stage, g_embed, g_fnorm, g_head,
        ), None

    zeros_mb = jnp.zeros(mb_shape, x_mb.dtype)
    init = (
        zeros_mb,
        zeros_mb,
        jnp.zeros((n_slots, *mb_shape), x_mb.dtype),
        jnp.zeros((s, *mb_shape), x_mb.dtype),
        jnp.zeros((), jnp.float32),
        jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), stage_params
        ),
        jnp.zeros((vocab, d_model), jnp.float32),
        jnp.zeros(head_leaves["final_norm"].shape, jnp.float32),
        jnp.zeros(head_leaves["head"].shape, jnp.float32),
    )
    (
        _, _, _, _, loss_sum, g_stage, g_embed, g_fnorm, g_head
    ), _ = jax.lax.scan(tick, init, jnp.arange(m + 3 * (s - 1)))

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
    g_stage = jax.tree.map(lambda g: g[None], g_stage)
    return loss_sum, g_stage, g_embed, g_fnorm, g_head


def pipeline_zb1_value_and_grad(
    params: dict,
    batch: dict | jax.Array,
    cfg: LlamaConfig,
    pipe: PipelineConfig,
    mesh: Mesh,
    backend: Optional[str] = None,
    loss_chunk_size: Optional[int] = None,
    loss_chunk_dtype=None,
) -> tuple[jax.Array, dict]:
    """(mean token loss, grads) through the zero-bubble H1 schedule —
    drop-in counterpart of ``pipeline_1f1b_value_and_grad`` (same
    canonical ``[S, ...]`` stage layout)."""
    from tpufw.train.trainer import shift_and_mask

    _check_1f1b(cfg, mesh)
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
    stage_specs = stage_partition_specs(params["stages"])
    hl_specs = {"final_norm": P(), "head": P()}
    scatter, embed_spec, head_spec = vocab_scatter_plan(
        params["head"].shape[-1], mesh
    )
    local = partial(
        _zb1_local,
        cfg=cfg,
        backend=backend,
        n_microbatches=m,
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
