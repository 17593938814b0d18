"""1F1B pipeline schedule: O(stages) activation memory, manual VJP.

The GPipe schedule (``tpufw.parallel.pipeline``) differentiates the
whole microbatch stream with autodiff, so every in-flight tick's stage
input is a saved residual — peak activation memory grows with the
microbatch count M. This module implements the 1F1B (one-forward-
one-backward) discipline instead: each device interleaves one forward
sub-tick and one backward sub-tick per schedule tick, a microbatch's
backward starts as soon as its loss gradient exists, and a stage input
is stashed only for the ticks its own backward is in flight — a ring
buffer of 2S slots, INDEPENDENT of M. Backward recomputes the stage
forward from the stashed input (full remat, the same trade the bench's
winning ``remat_policy="nothing"`` makes), so steady-state compute is
1 fwd + 1 recompute+bwd per tick — identical total FLOPs to GPipe with
full remat.

Schedule algebra (S stages, M microbatches, ticks t = 0 .. M+2S-3):
  - stage s runs the FORWARD of microbatch ``t - s`` (when in [0, M));
  - stage s runs the BACKWARD of microbatch ``t - 2(S-1) + s``;
  - the last stage's forward of microbatch j lands at tick j + S - 1,
    and its backward of j is at the SAME tick: the per-microbatch loss
    gradient (embed -> stages -> final norm -> head -> CE all live
    INSIDE the shard_map region) feeds straight into the backward ring.
  - both handoffs are produced at tick t-1 and consumed at t: one
    forward ``ppermute`` (s -> s+1) and one cotangent ``ppermute``
    (s -> s-1) per tick. Both are ISSUED so they overlap compute: the
    forward send right after the stage forward (before the epilogue
    and backward math), and the cotangent send deferred — the raw dx
    rides the carry and is permuted at the TOP of the next tick, ahead
    of that tick's forward — so the compiler can hide each transfer
    behind roughly half a tick of block math instead of serializing it
    at the scan-body boundary.
  - a stash written at tick j + s is read at tick j + 2(S-1) - s:
    lifetime <= 2(S-1) ticks, so ``j mod 2S`` slots never collide.

Whole-model gradients come out of one ``lax.scan``: stage-stack grads
accumulate locally (sharded exactly like the stage params); embed /
final-norm / head grads accumulate as masked zeros on non-owning
stages and one cross-axis psum makes them exact. Gradient parity with
the GPipe+autodiff path is pinned by tests/test_pipeline_1f1b.py —
the two schedules must produce the SAME gradients (both are exact).

Memory accounting: "O(stages)" is the ACTIVATION claim. The embed and
head gradient accumulators are full fp32 [V, D]/[D, V] buffers per
device while the scan runs — the scatter-add into the embed grad needs
the full vocab axis, so the carry can't shard it. What CAN shard is
the epilogue: when the vocab divides the pipe x data x fsdp shard
count, the final cross-device reduction is a ``psum_scatter`` instead
of a ``psum``, so the grads LEAVE the region vocab-sharded — 1/(P*D*F)
of the buffer per device from the region boundary onward (same wire
bytes as the psum's reduce-scatter phase, minus its all-gather). The
optimizer update then runs on the sharded grads; XLA re-replicates
only at the param write. Non-divisible vocabs fall back to the plain
replicated psum, decided statically at trace time.

Scope: Llama-family blocks incl. Qwen qkv biases (the shared _block
carries them), composed with data/fsdp batch sharding and Megatron
tensor parallelism. Gemma pairs and MoE are rejected loudly (GPipe
supports them; extend here the same way).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from tpufw.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_PIPE,
    AXIS_SEQUENCE,
    AXIS_TENSOR,
)
from tpufw.models.llama import LlamaConfig
from tpufw.ops import rms_norm
from tpufw.parallel.pipeline import (
    PipelineConfig,
    _block,
    _is_gemma,
    _is_mla,
    _is_moe,
    _mla_block,
    stage_partition_specs,
)

# ----------------------------------------------------------------------
# Megatron f/g operators — manual-VJP-safe tensor-parallel collectives
# ----------------------------------------------------------------------
#
# GPipe differentiates the whole shard_map region from OUTSIDE, where
# shard_map's transpose machinery gets psum cotangents right. This
# module calls jax.vjp INSIDE the region, where a plain lax.psum
# transposes to another psum (doubling the cotangent) and the
# rank-varying input cotangent is silently wrong (measured: all stage
# grads diverge under tensor>1). The fix is the classic Megatron
# algebra, stated as custom VJPs: the row-parallel combine ("g") is
# psum forward / identity backward, and the column-parallel entry
# ("f") is identity forward / psum backward. With activations
# replicated across ``tensor``, the local VJP then yields exactly the
# global gradients: sharded weight grads stay local shards, replicated
# leaves (norm scales) come out FULL on every rank (so they are NOT
# psummed over tensor in the accumulation below).


@jax.custom_vjp
def _g_combine(y: jax.Array) -> jax.Array:
    return jax.lax.psum(y, AXIS_TENSOR)


def _g_fwd(y):
    return jax.lax.psum(y, AXIS_TENSOR), None


def _g_bwd(_, ct):
    return (ct,)


_g_combine.defvjp(_g_fwd, _g_bwd)


@jax.custom_vjp
def _f_enter(x: jax.Array) -> jax.Array:
    return x


def _f_fwd(x):
    return x, None


def _f_bwd(_, ct):
    return (jax.lax.psum(ct, AXIS_TENSOR),)


_f_enter.defvjp(_f_fwd, _f_bwd)


def _stage_1f1b(stage_params, x, cfg, backend, seg, tp: bool):
    """The SAME Llama / DeepSeek-MLA block as the GPipe schedule
    (pipeline._block / pipeline._mla_block), with the tensor-parallel
    collectives routed through the f/g operators above so in-region
    ``jax.vjp`` transposes them exactly. tp=False inserts no
    collectives and is bit-identical to GPipe's."""
    tp_ops = (_f_enter, _g_combine) if tp else None
    blk = _mla_block if _is_mla(cfg) else _block

    def body(h, layer_p):
        return blk(layer_p, h, cfg, backend, seg, tp, tp_ops), None

    out, _ = jax.lax.scan(body, x, stage_params)
    return out


def _check_1f1b(cfg, mesh: Mesh) -> None:
    if _is_gemma(cfg) or _is_moe(cfg) or (_is_mla(cfg) and cfg.moe):
        raise NotImplementedError(
            "schedule='1f1b' implements Llama-family and dense "
            "DeepSeek-MLA blocks; use the GPipe schedule for "
            "Gemma/Mixtral"
        )
    for ax in (AXIS_SEQUENCE, AXIS_EXPERT):
        if mesh.shape[ax] != 1:
            raise NotImplementedError(
                f"1f1b composes with data/fsdp/tensor; mesh axis {ax} "
                f"has size {mesh.shape[ax]}"
            )


def _embed_fwd(embed: jax.Array, tokens: jax.Array, dtype) -> jax.Array:
    return embed.astype(dtype)[tokens]


#: Axes the embed/head grad reduction sums over (all ranks hold
#: masked partial sums; ``tensor`` is excluded — the f/g VJP algebra
#: already leaves those grads full on every tensor rank).
_VOCAB_REDUCE_AXES = (AXIS_PIPE, AXIS_DATA, AXIS_FSDP)


def vocab_scatter_plan(vocab: int, mesh: Mesh):
    """Static decision for the embed/head grad epilogue: returns
    ``(scatter, embed_spec, head_spec)``. ``scatter=True`` means the
    in-region reduction is a ``psum_scatter`` over the pipe x data x
    fsdp product and the grads leave the region sharded on their vocab
    axis (embed [V, D] on dim 0, head [D, V] on dim 1); ``False``
    falls back to the replicated psum (vocab not divisible, or a
    single shard where scatter is pointless)."""
    n = (
        mesh.shape[AXIS_PIPE]
        * mesh.shape[AXIS_DATA]
        * mesh.shape[AXIS_FSDP]
    )
    if n > 1 and vocab % n == 0:
        return (
            True,
            P(_VOCAB_REDUCE_AXES, None),
            P(None, _VOCAB_REDUCE_AXES),
        )
    return False, P(), P()


def _epilogue_loss(
    head_leaves: dict,
    hidden: jax.Array,
    targets: jax.Array,
    mask: jax.Array,
    cfg,
    loss_chunk_size: Optional[int],
    loss_chunk_dtype=None,
) -> jax.Array:
    """final RMSNorm -> LM head -> SUM token CE for one microbatch.
    Returns the unnormalized sum (token normalization happens once,
    globally, after the schedule)."""
    from tpufw.ops.loss import token_cross_entropy

    h = rms_norm(hidden, head_leaves["final_norm"], cfg.rms_eps)
    if loss_chunk_size:
        from tpufw.ops.loss import chunked_cross_entropy

        loss_mean, n = chunked_cross_entropy(
            h, head_leaves["head"], targets, mask,
            chunk_size=loss_chunk_size,
            compute_dtype=loss_chunk_dtype or jnp.bfloat16,
        )
        return loss_mean * n
    logits = h.astype(jnp.float32) @ head_leaves["head"].astype(
        jnp.float32
    )
    ce = token_cross_entropy(logits, targets)
    return (ce * mask).sum()


def _1f1b_local(
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
    """Per-device schedule body (inside shard_map).

    x_mb/tok_mb: [M, mb, T(, D)] embedded inputs + token ids;
    tgt_mb/mask_mb: [M, mb, T] shifted targets + loss mask; seg_mb is
    () or one [M, mb, T] segment-id array. Returns (loss_sum, stage
    grads, embed grad, final-norm grad, head grad) — all unnormalized
    sums over this device's rows; caller psums/normalizes.
    """
    s = axis_size(AXIS_PIPE)
    sidx = jax.lax.axis_index(AXIS_PIPE)
    tp = axis_size(AXIS_TENSOR) > 1
    stage_params = jax.tree.map(lambda a: a[0], stage_params)
    m = n_microbatches
    d_model = x_mb.shape[-1]
    mb_shape = x_mb.shape[1:]  # [mb, T, D]
    fwd_perm = [(i, (i + 1) % s) for i in range(s)]
    bwd_perm = [(i, (i - 1) % s) for i in range(s)]
    has_seg = bool(seg_mb)
    seg_all = seg_mb[0] if has_seg else None
    n_slots = 2 * s

    def stage_fwd(p, x, seg):
        return _stage_1f1b(p, x, cfg, backend, seg, tp)

    def mb_loss(hl, hidden, jf):
        return _epilogue_loss(
            hl,
            hidden,
            tgt_mb[jf],
            mask_mb[jf],
            cfg,
            loss_chunk_size,
            loss_chunk_dtype,
        )

    vocab = head_leaves["head"].shape[-1]

    def tick(carry, t):
        (
            f_recv, dx_prev, stash, loss_sum,
            g_stage, g_embed, g_fnorm, g_head,
        ) = carry
        jf = t - sidx                   # forward microbatch index
        jb = t - 2 * (s - 1) + sidx     # backward microbatch index
        f_on = (jf >= 0) & (jf < m)
        b_on = (jb >= 0) & (jb < m)
        jf_c = jnp.clip(jf, 0, m - 1)
        jb_c = jnp.clip(jb, 0, m - 1)

        # Cotangent handoff for THIS tick, issued first: the transfer
        # overlaps the forward sub-tick's block math below (the value
        # was computed last tick; only the wire time remains).
        b_recv = jax.lax.ppermute(dx_prev, AXIS_PIPE, bwd_perm)

        # ---- forward sub-tick -------------------------------------
        x_in = jnp.where(sidx == 0, x_mb[jf_c], f_recv)
        seg_f = seg_all[jf_c] if has_seg else None
        y = stage_fwd(stage_params, x_in, seg_f)
        # Forward handoff issued as soon as y exists — it overlaps the
        # epilogue + backward math of the rest of this tick.
        f_send = jax.lax.ppermute(y, AXIS_PIPE, fwd_perm)
        # Write-guard: inactive sub-ticks clip jf to 0 / m-1, whose
        # slots may hold a LIVE stash (e.g. mb m-1 awaits its backward
        # while drain ticks keep clipping to it) — keep the old value.
        slot_f = jf_c % n_slots
        old_slot = jax.lax.dynamic_index_in_dim(
            stash, slot_f, 0, keepdims=False
        )
        stash = jax.lax.dynamic_update_index_in_dim(
            stash, jnp.where(f_on, x_in, old_slot), slot_f, 0
        )

        # Last stage: this microbatch's loss + cotangent, NOW. Gated
        # with lax.cond — the head fwd+bwd is comparable to a whole
        # stage forward at real vocab sizes, and only one of S stages
        # uses the result; inside shard_map the scalar predicate stays
        # real control flow, so the other S-1 stages skip it at
        # runtime.
        def head_loss(hl, hidden):
            return mb_loss(hl, hidden, jf_c)

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

        # ---- backward sub-tick ------------------------------------
        # Cotangent in: the last stage's own loss grad for jb (== jf
        # there, same tick); everyone else consumes the ring.
        g_in = jnp.where(is_last, dy_j.astype(x_in.dtype), b_recv)
        x_stash = jax.lax.dynamic_index_in_dim(
            stash, jb_c % n_slots, 0, keepdims=False
        )
        seg_b = seg_all[jb_c] if has_seg else None
        _, stage_vjp = jax.vjp(
            lambda p, x: stage_fwd(p, x, seg_b), stage_params, x_stash
        )
        dp_j, dx_j = stage_vjp(g_in)
        g_stage = jax.tree.map(
            lambda acc, g: acc + jnp.where(b_on, g, 0.0), g_stage, dp_j
        )
        # Stage 0's dx backprops through the embedding lookup:
        # masked scatter-add straight into the carry (no [V, D]
        # intermediate per tick).
        g_embed = g_embed.at[tok_mb[jb_c]].add(
            jnp.where((sidx == 0) & b_on, dx_j, 0.0).astype(
                g_embed.dtype
            )
        )

        # f_send is in flight since the forward sub-tick; the raw dx
        # rides the carry and is permuted at the top of the NEXT tick
        # (same value the old tail-of-tick ppermute delivered, but the
        # send no longer serializes against this tick's compute).
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
    (
        _, _, _, loss_sum, g_stage, g_embed, g_fnorm, g_head
    ), _ = jax.lax.scan(tick, init, jnp.arange(m + 2 * s - 2))

    # Make every accumulator exact across the mesh:
    # - loss / replicated-param grads: sum over pipe (masked zeros on
    #   non-owning stages) and over the batch shards (data, fsdp).
    # - stage grads: sharded over pipe (+tensor per leaf), so psum over
    #   the batch shards only; replicated stage leaves (norms) also
    #   need the tensor sum. d_model axes: no sum (sharded).
    batch_axes = (AXIS_DATA, AXIS_FSDP)
    loss_sum = jax.lax.psum(loss_sum, (AXIS_PIPE, *batch_axes))
    g_fnorm = jax.lax.psum(g_fnorm, (AXIS_PIPE, *batch_axes))
    # Embed/head grads: reduce-scatter onto the vocab axis when the
    # plan allows (see ``vocab_scatter_plan``) so the [V, D]/[D, V]
    # fp32 buffers leave the region sharded; otherwise the replicated
    # psum. ``vocab_scatter`` is static — one branch traces.
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
    # The f/g custom VJPs make replicated leaves' grads (norm scales)
    # FULL on every tensor rank already — only the batch-shard sum is
    # needed; sharded leaves' grads are their local shards as-is.
    g_stage = jax.tree.map(
        lambda g: jax.lax.psum(g, batch_axes), g_stage
    )
    # Re-add the leading local stage axis the in_spec stripped.
    g_stage = jax.tree.map(lambda g: g[None], g_stage)
    return loss_sum, g_stage, g_embed, g_fnorm, g_head


def pipeline_1f1b_value_and_grad(
    params: dict,
    batch: dict | jax.Array,
    cfg: LlamaConfig,
    pipe: PipelineConfig,
    mesh: Mesh,
    backend: Optional[str] = None,
    loss_chunk_size: Optional[int] = None,
    loss_chunk_dtype=None,
) -> tuple[jax.Array, dict]:
    """(mean token loss, grads) through the 1F1B schedule — the drop-in
    counterpart of ``jax.value_and_grad(pipeline_loss)`` with O(S)
    activation memory. ``batch`` is {tokens [+ segment_ids,
    loss_mask]} or a bare token array."""
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
    # The embed kernel stays OUTSIDE the region (fwd is the host-side
    # lookup above; its grad is the scatter-add of stage 0's input
    # cotangents, accumulated inside) so the epilogue VJP never
    # materializes a [V, D] zero cotangent per tick.
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
        _1f1b_local,
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
