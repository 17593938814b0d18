"""Pipeline parallelism: GPipe schedule over the ``pipe`` mesh axis.

The reference implements no parallelism of any kind (SURVEY.md §2c); tpufw
treats the device mesh as the communication backend, and this module adds
the pipeline dimension: the layer stack is split into S stages, each stage
owned by one rank of the ``pipe`` mesh axis, and microbatches stream
through the stages with activations handed off by ``lax.ppermute`` —
point-to-point neighbor traffic, the cheapest collective on the mesh.

TPU-first shape of the implementation:
- the schedule is a ``lax.scan`` over M + S - 1 ticks inside one
  ``shard_map`` region — no per-tick Python, one compiled program, and
  the backward pass (autodiff through scan + ppermute) is the reverse
  schedule for free. Bubble fraction is (S-1)/(M+S-1): pick
  ``n_microbatches >> n_stages``.
- stage parameters are stacked on a leading [S] axis sharded over
  ``pipe`` — each device materializes only its own stage's layers.
- within a stage, layers run under ``lax.scan`` over a [layers_per_stage]
  axis (same one-block-compile property as the flax trunk).
- composes with data parallelism (microbatch rows sharded over
  (``data``, ``fsdp``)), tensor parallelism (Megatron head/ffn split
  inside each stage, two psums per block), and — for Mixtral — expert
  parallelism (expert stacks sharded over ``expert``, dispatch sliced
  to local experts, one psum combines); ``sequence`` must be 1.

The block math matches ``tpufw.models.llama`` (RMSNorm -> GQA attention
with RoPE -> SwiGLU) / ``tpufw.models.mixtral`` (routed MoE MLP via the
shared ``tpufw.ops.moe`` routing algebra), reusing the same functional
ops (``tpufw.ops.rms_norm`` / ``multi_head_attention`` /
``tpufw.models.llama.apply_rope``), so a pipeline stage is numerically
the same transformer block — pinned by the parity tests
(tests/test_pipeline.py, tests/test_pipeline_moe.py) against a
sequential evaluation of the identical parameters.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpufw.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_PIPE,
    AXIS_TENSOR,
)
from tpufw.models.llama import LlamaConfig, apply_rope
from tpufw.ops import multi_head_attention, rms_norm
from tpufw.ops.moe import expert_capacity, route_topk_capacity


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Pipeline schedule hyperparameters on top of a LlamaConfig.

    ``schedule``: "gpipe" (autodiff through the microbatch stream;
    activation memory grows with n_microbatches; supports Llama, Gemma,
    Mixtral incl. expert parallelism), "1f1b" (manual-VJP
    one-forward-one-backward, O(n_stages) activation memory — see
    tpufw.parallel.pipeline_1f1b; Llama-family, data/fsdp/tensor),
    "interleaved" (1F1B over ``n_virtual`` non-contiguous model chunks
    per device — bubble shrinks by the virtual-stage factor, see
    tpufw.parallel.pipeline_interleaved), or "zb1" (ZB-H1-style
    zero-bubble 1F1B: backward split into input-grad and weight-grad
    phases, weight grads scheduled into former drain-bubble ticks —
    see tpufw.parallel.pipeline_zb1).

    ``n_virtual`` is the interleaved schedule's virtual-stage count v:
    each device owns v chunks of n_layers/(v*n_stages) layers, stacked
    ``[v, S, layers_per_chunk, ...]`` (the leading [v] axis replicated,
    [S] sharded over ``pipe``). Other schedules keep v == 1 and the
    canonical ``[S, layers_per_stage, ...]`` stacks."""

    n_stages: int
    n_microbatches: int
    schedule: str = "gpipe"
    n_virtual: int = 1

    @property
    def virtual_layout(self) -> bool:
        """True when stage stacks carry the leading [n_virtual] axis."""
        return self.schedule == "interleaved"

    def validate(self, model: LlamaConfig, batch_size: int) -> None:
        if self.schedule not in ("gpipe", "1f1b", "interleaved", "zb1"):
            raise ValueError(
                f"unknown pipeline schedule {self.schedule!r}; "
                "expected 'gpipe', '1f1b', 'interleaved', or 'zb1'"
            )
        _check_model_split(model, self.n_stages)
        if batch_size % self.n_microbatches:
            raise ValueError(
                f"batch {batch_size} not divisible by "
                f"{self.n_microbatches} microbatches"
            )
        if self.schedule == "interleaved":
            v, s = self.n_virtual, self.n_stages
            if v < 2:
                raise ValueError(
                    "schedule='interleaved' needs n_virtual >= 2 "
                    "(v == 1 is exactly the '1f1b' schedule)"
                )
            if model.n_layers % (v * s):
                raise ValueError(
                    f"n_layers={model.n_layers} not divisible by "
                    f"n_virtual*n_stages={v * s} model chunks"
                )
            if self.n_microbatches % s:
                raise ValueError(
                    f"interleaved schedule groups microbatches by "
                    f"stage count: n_microbatches="
                    f"{self.n_microbatches} % n_stages={s} != 0"
                )
        elif self.n_virtual != 1:
            raise ValueError(
                f"n_virtual={self.n_virtual} only applies to "
                "schedule='interleaved'"
            )

    def bubble_fraction(self) -> float:
        """Analytic bubble fraction in the classic accounting (idle
        time / schedule time with fwd+bwd counted per microbatch):
        GPipe/1F1B (S-1)/(M+S-1); interleaved divides the fill by the
        virtual-stage factor, (S-1)/(vM+S-1); ZB-H1 splits the
        backward into thirds (F = B = W) and refills the bubble with
        deferred W, (S-1)/(3M+S-1). zb1 <= interleaved for v <= 3."""
        s, m = self.n_stages, self.n_microbatches
        if self.schedule == "interleaved":
            return (s - 1) / (self.n_virtual * m + s - 1)
        if self.schedule == "zb1":
            return (s - 1) / (3 * m + s - 1)
        return (s - 1) / (m + s - 1)

    def n_ticks(self) -> int:
        """Scan ticks per train step — each one fwd and/or bwd slot on
        every device plus the ring handoffs. GPipe runs separate fwd
        and bwd sweeps of M+S-1; 1F1B fuses them into M+2(S-1)
        fwd/bwd tick-pairs; interleaved stretches by the chunk factor
        to vM+(v+1)S-2; ZB-H1's three phases drain in M+3(S-1). The
        host-side ``pipeline_tick`` span divides the step wall by this
        (docs/OBSERVABILITY.md)."""
        s, m = self.n_stages, self.n_microbatches
        if self.schedule == "gpipe":
            return 2 * (m + s - 1)
        if self.schedule == "interleaved":
            v = self.n_virtual
            return v * m + (v + 1) * s - 2
        if self.schedule == "zb1":
            return m + 3 * (s - 1)
        return m + 2 * (s - 1)


# ----------------------------------------------------------------------
# Parameters
# ----------------------------------------------------------------------


def _is_moe(cfg) -> bool:
    """MixtralConfig subclasses LlamaConfig: every pipeline entry point
    must branch on this or it would silently build DENSE llama stacks
    (no experts, no router) from an MoE config."""
    from tpufw.models.mixtral import MixtralConfig

    return isinstance(cfg, MixtralConfig)


def _is_gemma(cfg) -> bool:
    from tpufw.models.gemma import GemmaConfig

    return isinstance(cfg, GemmaConfig)


def _is_mla(cfg) -> bool:
    """DeepseekConfig: MLA attention (latent KV factorization), its own
    dataclass — NOT a LlamaConfig subclass, so every dispatch must
    branch here before touching n_kv_heads/head_dim (MLA has neither)."""
    from tpufw.models.deepseek import DeepseekConfig

    return isinstance(cfg, DeepseekConfig)


def _returns_aux(cfg) -> bool:
    """Configs whose forward returns (logits, router aux): Mixtral and
    MoE-FFN DeepSeek. Every aux-threading branch keys off this ONE
    predicate so a new MoE family can't half-plumb."""
    return _is_moe(cfg) or (_is_mla(cfg) and cfg.moe)


def _check_model_split(cfg, n_stages: int) -> None:
    """Model-side pipelineability checks, shared by
    ``PipelineConfig.validate`` (trainer path) and
    ``init_pipeline_params`` (direct callers) so the two can't drift:
    an unchecked config silently builds a truncated or wrong-family
    model."""
    if not (
        isinstance(cfg, LlamaConfig) or _is_gemma(cfg) or _is_mla(cfg)
    ):
        # A foreign config would silently build Llama-shaped stages —
        # wrong model, no error until (at best) a missing attribute
        # deep in init.
        raise NotImplementedError(
            f"pipeline schedules implement Llama-family, Gemma, and "
            f"DeepSeek-MLA blocks; got {type(cfg).__name__}"
        )
    if _is_mla(cfg) and cfg.moe and cfg.first_k_dense > 0:
        # Uniform MoE stacks pipeline fine (_mla_moe_block); mixing
        # dense and routed layers per first_k_dense does not fit the
        # homogeneous per-stage stacks — building it would silently
        # drop the dense/MoE structure.
        raise NotImplementedError(
            "pipelined MLA-MoE stages need UNIFORM layers "
            f"(first_k_dense == 0, got {cfg.first_k_dense}); mixed "
            "dense/MoE stacks use the flax trainer"
        )
    if not getattr(cfg, "causal", True):
        # Both schedules hardcode causal attention; silently training
        # a causal model under a bidirectional config would be the
        # quiet version of wrong.
        raise NotImplementedError(
            "pipeline schedules implement causal attention only; "
            "bidirectional (causal=False) embedding fine-tuning uses "
            "the plain Trainer (tpufw.train.contrastive)"
        )
    if _is_moe(cfg) and getattr(cfg, "attention_qkv_bias", False):
        # The MoE stage stacks don't carry bias leaves; building this
        # config would silently drop the biases.
        raise NotImplementedError(
            "pipelined MoE blocks do not implement attention_qkv_bias"
        )
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by "
            f"{n_stages} stages"
        )
    if _is_gemma(cfg) and (cfg.n_layers // n_stages) % 2:
        raise ValueError(
            f"Gemma pipelines scan local/global PAIRS: layers per "
            f"stage ({cfg.n_layers}/{n_stages}) must be even"
        )


def to_virtual_stages(stages: dict, n_virtual: int, n_stages: int):
    """Regroup stage stacks into the interleaved ``[v, S, lpc, ...]``
    layout. Accepts the canonical ``[S, lps, ...]`` stacks (or any
    ``[a, b, ...]`` leading pair with a*b == n_layers-per-leaf): the
    leading two axes flatten to layer order, then regroup so chunk
    c = k*S + d lands at ``[k, d]`` — device d (pipe rank) owns the
    round-robin chunks d, S+d, 2S+d, ... A pure reshape: on replicated
    arrays it is free; on pipe-sharded arrays XLA inserts the
    re-layout collective once (param conversion, not a per-step op)."""

    def conv(a):
        n_layers = a.shape[0] * a.shape[1]
        lpc = n_layers // (n_virtual * n_stages)
        return a.reshape(n_virtual, n_stages, lpc, *a.shape[2:])

    return jax.tree.map(conv, stages)


def to_canonical_stages(stages: dict, n_stages: int):
    """Inverse of :func:`to_virtual_stages`: ``[v, S, lpc, ...]`` back
    to contiguous ``[n_stages, lps, ...]`` stacks (layer order is the
    flattened [v, S, lpc] index order by construction)."""
    return jax.tree.map(
        lambda a: a.reshape(n_stages, -1, *a.shape[3:]), stages
    )


def init_pipeline_params(
    key: jax.Array, cfg: LlamaConfig, pipe: PipelineConfig
) -> dict:
    """Explicit param pytree; stage weights stacked on a leading [S] axis.

    Initializers match the flax trunk (normal embed, lecun-style fan-in
    scaling elsewhere); stored in ``cfg.param_dtype``. The interleaved
    schedule builds the same layer sequence, regrouped into its
    ``[n_virtual, S, layers_per_chunk, ...]`` stacks.
    """
    flat = pipe
    if pipe.virtual_layout:
        # Same layer sequence as a v*S-stage flat pipeline with the
        # same key — the regroup below is a pure reshape, so flat and
        # virtual inits are bit-identical per layer.
        flat = dataclasses.replace(
            pipe,
            n_stages=pipe.n_stages * pipe.n_virtual,
            schedule="1f1b",
            n_virtual=1,
        )
    params = _init_flat_pipeline_params(key, cfg, flat)
    if pipe.virtual_layout:
        params["stages"] = to_virtual_stages(
            params["stages"], pipe.n_virtual, pipe.n_stages
        )
    return params


def _init_flat_pipeline_params(
    key: jax.Array, cfg: LlamaConfig, pipe: PipelineConfig
) -> dict:
    """Canonical [S, lps, ...] init body (every schedule but the
    virtual-layout one; the interleaved wrapper above regroups it)."""
    s = pipe.n_stages
    _check_model_split(cfg, s)
    lps = cfg.n_layers // s
    d, h, f = cfg.d_model, cfg.n_heads, cfg.d_ff
    # MLA configs have no n_kv_heads/head_dim (factorized projections).
    kh = getattr(cfg, "n_kv_heads", None)
    dh = getattr(cfg, "head_dim", None)
    keys = jax.random.split(key, 9)

    def w(k, shape, fan_in):
        return (
            jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(fan_in)
        ).astype(cfg.param_dtype)

    if _is_mla(cfg):
        # MLA factorized stacks — the functional mirror of
        # tpufw.models.deepseek.MLAttention's expanded/training form
        # (deepseek.py:329): shared latent down-projections (wkv_a,
        # plus wq_a for the compressed-q path) with their RMSNorms,
        # head-expanding up-projections (wq/wq_b, wkv_b), and the
        # dense SwiGLU MLP.
        kvr, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        stages = {
            "attn_norm": jnp.ones((s, lps, d), jnp.float32),
            "kv_a_norm": jnp.ones((s, lps, kvr), jnp.float32),
            "wkv_a": w(keys[2], (s, lps, d, kvr + dr), d),
            "wkv_b": w(
                keys[3],
                (s, lps, kvr, h, cfg.qk_nope_head_dim + cfg.v_head_dim),
                kvr,
            ),
            "wo": w(
                keys[4], (s, lps, h, cfg.v_head_dim, d),
                h * cfg.v_head_dim,
            ),
            "mlp_norm": jnp.ones((s, lps, d), jnp.float32),
        }
        if cfg.moe:
            # Routed stacks instead of the dense MLP ([E] axis after
            # the layer axis, like Mixtral); the always-on shared
            # experts are one fused SwiGLU of n_shared * moe_d_ff.
            # Built INSTEAD of the dense leaves — materializing dense
            # [S, lps, d, d_ff] stacks just to delete them would be a
            # multi-GB transient at real shapes.
            e, mf = cfg.n_routed_experts, cfg.moe_d_ff
            mkeys = jax.random.split(keys[5], 7)
            stages.update(
                router=w(mkeys[0], (s, lps, d, e), d),
                w_gate=w(mkeys[1], (s, lps, e, d, mf), d),
                w_up=w(mkeys[2], (s, lps, e, d, mf), d),
                w_down=w(mkeys[3], (s, lps, e, mf, d), mf),
            )
            if cfg.n_shared_experts:
                sf = cfg.n_shared_experts * mf
                stages.update(
                    w_shared_gate=w(mkeys[4], (s, lps, d, sf), d),
                    w_shared_up=w(mkeys[5], (s, lps, d, sf), d),
                    w_shared_down=w(mkeys[6], (s, lps, sf, d), sf),
                )
        else:
            stages.update(
                w_gate=w(keys[5], (s, lps, d, f), d),
                w_up=w(keys[6], (s, lps, d, f), d),
                w_down=w(keys[7], (s, lps, f, d), f),
            )
        if cfg.q_lora_rank is None:
            stages["wq"] = w(keys[1], (s, lps, d, h, cfg.qk_head_dim), d)
        else:
            qr = cfg.q_lora_rank
            qkeys = jax.random.split(keys[1], 2)
            stages["wq_a"] = w(qkeys[0], (s, lps, d, qr), d)
            stages["q_a_norm"] = jnp.ones((s, lps, qr), jnp.float32)
            stages["wq_b"] = w(
                qkeys[1], (s, lps, qr, h, cfg.qk_head_dim), qr
            )
        return {
            "embed": jax.random.normal(
                keys[0], (cfg.vocab_size, d), jnp.float32
            ).astype(cfg.param_dtype),
            "stages": stages,
            "final_norm": jnp.ones((d,), jnp.float32),
            "head": w(keys[8], (d, cfg.vocab_size), d),
        }

    if _is_gemma(cfg):
        # Pair layout (local sliding-window block + global block), the
        # functional mirror of tpufw.models.gemma.GemmaPair: stage
        # stacks are [S, pairs_per_stage, ...]; sandwich norms store the
        # (1 + w) offset (zeros init); embeddings are tied (no head)
        # and stored at 1/sqrt(d) for the sqrt(d) lookup scaling.
        pairs = lps // 2

        def block(k):
            ks = jax.random.split(k, 7)
            return {
                "pre_attn_norm": jnp.zeros((s, pairs, d), jnp.float32),
                "post_attn_norm": jnp.zeros((s, pairs, d), jnp.float32),
                "pre_mlp_norm": jnp.zeros((s, pairs, d), jnp.float32),
                "post_mlp_norm": jnp.zeros((s, pairs, d), jnp.float32),
                "wq": w(ks[0], (s, pairs, d, h, dh), d),
                "wk": w(ks[1], (s, pairs, d, kh, dh), d),
                "wv": w(ks[2], (s, pairs, d, kh, dh), d),
                "wo": w(ks[3], (s, pairs, h, dh, d), h * dh),
                "w_gate": w(ks[4], (s, pairs, d, f), d),
                "w_up": w(ks[5], (s, pairs, d, f), d),
                "w_down": w(ks[6], (s, pairs, f, d), f),
            }

        return {
            "embed": (
                jax.random.normal(
                    keys[0], (cfg.vocab_size, d), jnp.float32
                )
                / math.sqrt(d)
            ).astype(cfg.param_dtype),
            "stages": {
                "local": block(keys[1]),
                "global": block(keys[2]),
            },
            "final_norm": jnp.zeros((d,), jnp.float32),
        }

    if _is_moe(cfg):
        # Expert stacks carry an [E] axis after the layer axis —
        # [S, lps, E, in, out] — which stage_partition_specs maps onto
        # the ``expert`` mesh axis (pp x ep). The router stays
        # replicated: its logits must cover ALL experts on every rank
        # so the capacity/slot assignment agrees globally.
        e = cfg.n_experts
        mkeys = jax.random.split(keys[8], 3)
        return {
            "embed": jax.random.normal(
                keys[0], (cfg.vocab_size, d), jnp.float32
            ).astype(cfg.param_dtype),
            "stages": {
                "attn_norm": jnp.ones((s, lps, d), jnp.float32),
                "wq": w(keys[1], (s, lps, d, h, dh), d),
                "wk": w(keys[2], (s, lps, d, kh, dh), d),
                "wv": w(keys[3], (s, lps, d, kh, dh), d),
                "wo": w(keys[4], (s, lps, h, dh, d), h * dh),
                "moe_norm": jnp.ones((s, lps, d), jnp.float32),
                "router": w(keys[5], (s, lps, d, e), d),
                "w_gate": w(keys[6], (s, lps, e, d, f), d),
                "w_up": w(keys[7], (s, lps, e, d, f), d),
                "w_down": w(mkeys[0], (s, lps, e, f, d), f),
            },
            "final_norm": jnp.ones((d,), jnp.float32),
            "head": w(mkeys[1], (d, cfg.vocab_size), d),
        }

    stages = {
        "attn_norm": jnp.ones((s, lps, d), jnp.float32),
        "wq": w(keys[1], (s, lps, d, h, dh), d),
        "wk": w(keys[2], (s, lps, d, kh, dh), d),
        "wv": w(keys[3], (s, lps, d, kh, dh), d),
        "wo": w(keys[4], (s, lps, h, dh, d), h * dh),
        "mlp_norm": jnp.ones((s, lps, d), jnp.float32),
        "w_gate": w(keys[5], (s, lps, d, f), d),
        "w_up": w(keys[6], (s, lps, d, f), d),
        "w_down": w(keys[7], (s, lps, f, d), f),
    }
    if getattr(cfg, "attention_qkv_bias", False):
        # Qwen-2 family: zero-init biases on q/k/v only (o and the MLP
        # stay bias-free), mirroring the flax Attention's projection
        # use_bias — tpufw/models/llama.py Attention.__call__.
        stages["bq"] = jnp.zeros((s, lps, h, dh), jnp.float32)
        stages["bk"] = jnp.zeros((s, lps, kh, dh), jnp.float32)
        stages["bv"] = jnp.zeros((s, lps, kh, dh), jnp.float32)
    return {
        "embed": jax.random.normal(
            keys[0], (cfg.vocab_size, d), jnp.float32
        ).astype(cfg.param_dtype),
        "stages": stages,
        "final_norm": jnp.ones((d,), jnp.float32),
        "head": w(keys[8], (d, cfg.vocab_size), d),
    }


#: Which axis of each stage-stack leaf shards over ``tensor``
#: (Megatron-style): q/k/v split output heads, o splits input heads,
#: gate/up split d_ff columns, down splits d_ff rows — so each block
#: needs exactly two psums (post-attention, post-MLP). Axes are counted
#: FROM THE END so one table covers the Llama ([S, lps, ...]), Gemma
#: ([S, pairs, ...]), and Mixtral expert ([S, lps, E, in, out]) stack
#: ranks: the contraction dims sit at fixed offsets from the tail in
#: all three layouts.
_TENSOR_LEAF_AXIS = {
    "wq": -2, "wk": -2, "wv": -2,  # [..., d, H, dh] -> head axis
    "wo": -3,                      # [..., H, dh, d] -> head axis
    "bq": -2, "bk": -2, "bv": -2,  # [..., H, dh] -> head axis (Qwen)
    "w_gate": -1, "w_up": -1,      # [..., d, f] -> ffn columns
    "w_down": -2,                  # [..., f, d] -> ffn rows
    # MLA head-expanding kernels split their head axis too; the latent
    # down-projections (wq_a, wkv_a) and latent norms stay REPLICATED —
    # the latents are shared across heads, and splitting them would put
    # an RMSNorm on a partial axis.
    "wq_b": -2,                    # [..., qr, H, qk] -> head axis
    "wkv_b": -2,                   # [..., kvr, H, dn+dv] -> head axis
    # DeepSeek shared experts: one fused SwiGLU, Megatron-split like
    # the dense MLP.
    "w_shared_gate": -1, "w_shared_up": -1,
    "w_shared_down": -2,
}

#: Mixtral expert stacks are rank 5 ([S, lps, E, in, out]); their [E]
#: axis shards over ``expert`` (pp x ep). Dense w_* leaves are rank 4
#: and never match.
_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def stage_partition_specs(stages: dict, virtual: bool = False) -> Any:
    """Per-leaf PartitionSpecs for a stage-stack pytree: leading [S]
    axis over ``pipe``, the Megatron tensor split per
    ``_TENSOR_LEAF_AXIS``, and the expert split for rank-5 MoE stacks.
    Used both as ``shard_map`` in_specs and (via
    ``pipeline_param_shardings``) as the physical param layout, so the
    two can't disagree.

    ``virtual=True`` covers the interleaved ``[v, S, lpc, ...]`` layout:
    the pipe axis moves to position 1 (v chunks per device stay local,
    so axis 0 is unsharded). The tensor offsets still work — they count
    from the tail. Expert stacks never reach here (the interleaved
    schedule is dense-only), and the rank-5 expert test is skipped
    because a rank-5 *dense* leaf under the virtual layout would
    misfire on it."""

    def spec(path, leaf):
        name = next(
            (
                k.key
                for k in reversed(path)
                if isinstance(getattr(k, "key", None), str)
            ),
            "",
        )
        if virtual:
            axes: list = [None, AXIS_PIPE, *([None] * (leaf.ndim - 2))]
        else:
            axes = [AXIS_PIPE, *([None] * (leaf.ndim - 1))]
        t = _TENSOR_LEAF_AXIS.get(name)
        if t is not None:
            axes[leaf.ndim + t] = AXIS_TENSOR
        if not virtual and name in _EXPERT_LEAVES and leaf.ndim == 5:
            axes[2] = AXIS_EXPERT
        return P(*axes)

    return jax.tree_util.tree_map_with_path(spec, stages)


def pipeline_param_shardings(
    mesh: Mesh, params: dict, virtual: bool = False
) -> dict:
    """NamedShardings: stage stacks split over ``pipe`` (+ ``tensor``
    on head/ffn axes), rest replicated. ``virtual=True`` for the
    interleaved ``[v, S, ...]`` stacks (pipe on axis 1)."""
    rep = NamedSharding(mesh, P())
    out = {
        "embed": rep,
        "stages": jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            stage_partition_specs(params["stages"], virtual=virtual),
        ),
        "final_norm": rep,
    }
    if "head" in params:
        out["head"] = rep
    return out


# ----------------------------------------------------------------------
# Block / stage math (numerically the tpufw.models.llama block)
# ----------------------------------------------------------------------


def _tp_psum(y: jax.Array, tp: bool) -> jax.Array:
    """Combine row-parallel partial sums over ``tensor``. ``tp`` is a
    trace-time bool: False in the sequential oracle (no mesh axes
    bound) and on tensor=1 meshes (psum would be identity)."""
    return jax.lax.psum(y, AXIS_TENSOR) if tp else y


def _attn_sublayer(
    p: dict, x: jax.Array, cfg: LlamaConfig, backend: str, seg=None,
    tp: bool = False, tp_ops=None,
) -> jax.Array:
    """Pre-norm GQA attention with RoPE + residual add — the half of
    the decoder block shared verbatim by the dense (``_block``) and
    MoE (``_mixtral_block``) layouts. With ``tp`` the head axes of p
    are LOCAL shards; the output projection partial-sum is psummed.

    ``tp_ops`` overrides the two tensor-parallel collectives as an
    (enter, combine) pair — the 1F1B schedule substitutes Megatron f/g
    custom VJPs (pipeline_1f1b) because in-region ``jax.vjp`` cannot
    transpose a plain psum; GPipe's autodiff-from-outside uses the
    defaults (identity enter, plain psum combine)."""
    enter, combine = tp_ops or (
        (lambda h: h), (lambda y: _tp_psum(y, tp))
    )
    dt = cfg.dtype
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1]), x.shape[:2]
    )
    h = enter(rms_norm(x, p["attn_norm"], cfg.rms_eps))
    q = jnp.einsum("btd,dhk->bthk", h, p["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", h, p["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", h, p["wv"].astype(dt))
    if "bq" in p:  # Qwen qkv biases: added pre-RoPE, like the flax path
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    rs = getattr(cfg, "rope_scaling", None)
    q = apply_rope(q, positions, cfg.rope_theta, rs)
    k = apply_rope(k, positions, cfg.rope_theta, rs)
    att = multi_head_attention(
        q, k, v, causal=True, segment_ids=seg,
        # Mistral-style uniform window (None for plain Llama).
        sliding_window=getattr(cfg, "sliding_window", None),
        backend=backend,
    )
    return x + combine(
        jnp.einsum("bthk,hkd->btd", att, p["wo"].astype(dt))
    )


def _block(
    p: dict, x: jax.Array, cfg: LlamaConfig, backend: str, seg=None,
    tp: bool = False, tp_ops=None,
):
    """One decoder block; p leaves have no leading layer axis. With
    ``tp`` the head/ffn axes of p are LOCAL shards (Megatron split per
    ``_TENSOR_LEAF_AXIS``); the two partial-sum einsums are psummed
    (or routed through ``tp_ops`` — see ``_attn_sublayer``)."""
    enter, combine = tp_ops or (
        (lambda h: h), (lambda y: _tp_psum(y, tp))
    )
    dt = cfg.dtype
    x = _attn_sublayer(p, x, cfg, backend, seg, tp, tp_ops)
    h = enter(rms_norm(x, p["mlp_norm"], cfg.rms_eps))
    g = jnp.einsum("btd,df->btf", h, p["w_gate"].astype(dt))
    u = jnp.einsum("btd,df->btf", h, p["w_up"].astype(dt))
    x = x + combine(
        jnp.einsum(
            "btf,fd->btd", jax.nn.silu(g) * u, p["w_down"].astype(dt)
        )
    )
    return x


def _mla_attn_sublayer(
    p: dict, x: jax.Array, cfg, backend: str, seg=None,
    tp: bool = False, tp_ops=None,
):
    """MLA attention + residual, numerically the
    tpufw.models.deepseek.MLAttention expanded/training form — shared
    by the dense (``_mla_block``) and MoE (``_mla_moe_block``) layouts.
    Under ``tp`` the head axes of wq/wq_b/wkv_b/wo are LOCAL shards;
    the latent projections (wq_a, wkv_a) run replicated on every rank —
    their outputs are identical across ``tensor``, so the decoupled
    rope key and both latent RMSNorms agree globally, and the only
    collective is the output projection's combine."""
    from tpufw.models.deepseek import apply_rope_interleaved

    enter, combine = tp_ops or (
        (lambda h: h), (lambda y: _tp_psum(y, tp))
    )
    dt = cfg.dtype
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dv, kvr = cfg.v_head_dim, cfg.kv_lora_rank
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    # Megatron-f (``enter``) placement: at each COLUMN-PARALLEL input —
    # the operand of a head-sharded einsum — and NOT at the shared h.
    # The latent kernels (wq_a/wkv_a) are replicated, so their inputs
    # need no f; their OUTPUTS (cq, c_kv, k_pe) feed head-local math
    # whose per-rank cotangents are partial sums, and the f's backward
    # psum completes them exactly there. An f at h instead would leave
    # the latent params' grads unreduced (the 1F1B parity test caught
    # this) and double-count the latent path's h-contribution.
    h = rms_norm(x, p["attn_norm"], cfg.rms_eps)
    if "wq" in p:
        q = jnp.einsum("btd,dhk->bthk", enter(h), p["wq"].astype(dt))
    else:  # compressed-q path (V2-236B): q_a -> norm -> q_b
        cq = jnp.einsum("btd,dr->btr", h, p["wq_a"].astype(dt))
        cq = rms_norm(cq, p["q_a_norm"], cfg.rms_eps)
        q = jnp.einsum(
            "btr,rhk->bthk", enter(cq), p["wq_b"].astype(dt)
        )
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope_interleaved(
        q_pe, positions, cfg.rope_theta, cfg.rope_scaling
    )

    # Shared KV latent + decoupled-rope key (one "head").
    ckv_kr = jnp.einsum("btd,dr->btr", h, p["wkv_a"].astype(dt))
    c_kv = rms_norm(ckv_kr[..., :kvr], p["kv_a_norm"], cfg.rms_eps)
    k_pe = apply_rope_interleaved(
        ckv_kr[..., kvr:][:, :, None, :],
        positions, cfg.rope_theta, cfg.rope_scaling,
    )  # [B, T, 1, dr]
    k_pe = enter(k_pe)  # broadcast over LOCAL heads below
    kv = jnp.einsum(
        "btr,rhd->bthd", enter(c_kv).astype(dt), p["wkv_b"].astype(dt)
    )
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:3], dr))], axis=-1
    )
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    if backend in ("flash", "ring"):
        # v zero-padded to qk_head_dim, output sliced back — exact
        # (padded value columns contribute zeros), same discipline as
        # the flax MLAttention backend dispatch.
        v_in = jnp.pad(
            v, ((0, 0), (0, 0), (0, 0), (0, cfg.qk_head_dim - dv))
        )
    else:
        v_in = v
    att = multi_head_attention(
        q, k, v_in, causal=True, segment_ids=seg, backend=backend
    )
    if backend in ("flash", "ring"):
        att = att[..., :dv]
    return x + combine(
        jnp.einsum("bthd,hdD->btD", att, p["wo"].astype(dt))
    )


def _mla_block(
    p: dict, x: jax.Array, cfg, backend: str, seg=None,
    tp: bool = False, tp_ops=None,
):
    """One dense-FFN DeepSeek-MLA decoder block: the shared MLA
    attention sublayer + the standard SwiGLU MLP."""
    enter, combine = tp_ops or (
        (lambda h: h), (lambda y: _tp_psum(y, tp))
    )
    dt = cfg.dtype
    x = _mla_attn_sublayer(p, x, cfg, backend, seg, tp, tp_ops)
    hm = enter(rms_norm(x, p["mlp_norm"], cfg.rms_eps))
    g = jnp.einsum("btd,df->btf", hm, p["w_gate"].astype(dt))
    u = jnp.einsum("btd,df->btf", hm, p["w_up"].astype(dt))
    return x + combine(
        jnp.einsum(
            "btf,fd->btd", jax.nn.silu(g) * u, p["w_down"].astype(dt)
        )
    )


def _mla_moe_block(
    p: dict, x: jax.Array, cfg, backend: str, seg=None,
    tp: bool = False, ep: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One MoE-FFN DeepSeek-MLA decoder block (uniform stacks,
    first_k_dense == 0): the shared MLA attention sublayer + the
    DeepSeek MoE FFN — routed experts through the SAME ``_moe_mlp``
    dispatch algebra as pipelined Mixtral (V2 gate conventions: raw
    softmax mass, optional group-limited selection,
    routed_scaling_factor) plus the always-on shared-expert SwiGLU.
    Returns (x, router aux loss)."""
    x = _mla_attn_sublayer(p, x, cfg, backend, seg, tp)
    dt = cfg.dtype
    h = rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    y, aux = _moe_mlp(
        p, h, cfg, None if seg is None else seg > 0, tp, ep
    )
    y = y * cfg.routed_scaling_factor
    if "w_shared_gate" in p:
        g = jnp.einsum("btd,df->btf", h, p["w_shared_gate"].astype(dt))
        u = jnp.einsum("btd,df->btf", h, p["w_shared_up"].astype(dt))
        y = y + _tp_psum(
            jnp.einsum(
                "btf,fd->btd",
                jax.nn.silu(g) * u,
                p["w_shared_down"].astype(dt),
            ),
            tp,
        )
    return x + y, aux


def _moe_mlp(
    p: dict, h: jax.Array, cfg, valid, tp: bool, ep: bool
) -> tuple[jax.Array, jax.Array]:
    """Functional top-k MoE MLP over this device's LOCAL experts.

    Routing (``tpufw.ops.moe.route_topk_capacity`` — the SAME algebra
    as the flax MoEMLP, so the two paths can't drift) runs over ALL
    experts on every rank: the router kernel is replicated and the
    slot/capacity assignment must agree globally. Under ``ep`` each
    rank then slices the dispatch/combine tensors down to its own [E /
    ep] expert stack — no all-to-all is needed because the batch rides
    ``data``/``fsdp``, never ``expert``, so activations are already
    replicated across the expert axis and one psum combines the expert
    partial sums (+ the ``tp`` d_ff partial sums in the same
    collective).

    The routing group is this device's microbatch shard (G = local
    rows x T), i.e. capacity is per (microbatch, data-shard) group —
    the standard pipelined-MoE discipline; the flax path's group is
    the global batch.
    """
    b, t, d = h.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    g = b * t
    capacity = expert_capacity(g, k, e, cfg.capacity_factor)

    logits = jnp.einsum(
        "btd,de->bte",
        h.astype(jnp.float32),
        p["router"].astype(jnp.float32),
    ).reshape(g, e)
    dispatch, combine, aux, z = route_topk_capacity(
        logits, k, capacity,
        valid=None if valid is None else valid.reshape(g),
        dtype=cfg.dtype,
        # Mixtral renormalizes top-k mass; DeepSeek keeps the raw
        # softmax mass and may group-limit selection — both read off
        # the config so the flax and pipelined paths can't drift.
        norm_topk=getattr(cfg, "norm_topk_prob", True),
        group_limit=(
            (cfg.n_group, cfg.topk_group)
            if getattr(cfg, "n_group", 0)
            else None
        ),
    )

    if ep:
        e_local = p["w_gate"].shape[0]
        off = jax.lax.axis_index(AXIS_EXPERT) * e_local
        dispatch = jax.lax.dynamic_slice_in_dim(dispatch, off, e_local, 1)
        combine = jax.lax.dynamic_slice_in_dim(combine, off, e_local, 1)

    dt = cfg.dtype
    xf = h.reshape(g, d).astype(dt)
    xe = jnp.einsum("gec,gd->ecd", dispatch, xf)  # [E_local, C, d]
    gate = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dt))
    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(dt))
    down = jnp.einsum(
        "ecf,efd->ecd", jax.nn.silu(gate) * up, p["w_down"].astype(dt)
    )
    y = jnp.einsum("gec,ecd->gd", combine, down)
    axes = (AXIS_EXPERT,) * ep + (AXIS_TENSOR,) * tp
    if axes:
        y = jax.lax.psum(y, axes)
    aux_loss = cfg.router_aux_weight * aux + cfg.router_z_weight * z
    return y.reshape(b, t, d), aux_loss


def _mixtral_block(
    p: dict, x: jax.Array, cfg, backend: str, seg=None,
    tp: bool = False, ep: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One Mixtral decoder block (attention + routed MoE MLP); returns
    (x, router aux loss). ``valid`` for routing mirrors the flax
    MixtralBlock: padding rows of packed batches (segment id 0) are
    excluded from routing and capacity."""
    x = _attn_sublayer(p, x, cfg, backend, seg, tp)
    h = rms_norm(x, p["moe_norm"], cfg.rms_eps)
    y, aux = _moe_mlp(
        p, h, cfg, None if seg is None else seg > 0, tp, ep
    )
    return x + y, aux


def _gemma_block(p, x, cfg, backend, seg, window, tp: bool = False):
    """One Gemma-2 block (sandwich (1+w) norms, GeGLU, caps, qpas
    scaling) — the functional mirror of tpufw.models.gemma.GemmaBlock.
    Under ``tp`` the partial sums are combined BEFORE the post-norms
    (RMSNorm is nonlinear; psum must see the full activation)."""
    dt = cfg.dtype
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    def norm(which, h):
        return rms_norm(h, p[which] + 1.0, cfg.rms_eps)

    h = norm("pre_attn_norm", x)
    q = jnp.einsum("btd,dhk->bthk", h, p["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", h, p["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", h, p["wv"].astype(dt))
    rs = getattr(cfg, "rope_scaling", None)
    q = apply_rope(q, positions, cfg.rope_theta, rs)
    k = apply_rope(k, positions, cfg.rope_theta, rs)
    qpas = cfg.query_pre_attn_scalar
    if qpas is not None and float(qpas) != float(cfg.head_dim):
        q = q * (math.sqrt(cfg.head_dim) / math.sqrt(float(qpas)))
    att = multi_head_attention(
        q, k, v, causal=True, segment_ids=seg,
        logits_soft_cap=cfg.attn_logit_soft_cap,
        sliding_window=window,
        backend=backend,
    )
    x = x + norm(
        "post_attn_norm",
        _tp_psum(
            jnp.einsum("bthk,hkd->btd", att, p["wo"].astype(dt)), tp
        ),
    )
    h = norm("pre_mlp_norm", x)
    g = jnp.einsum("btd,df->btf", h, p["w_gate"].astype(dt))
    u = jnp.einsum("btd,df->btf", h, p["w_up"].astype(dt))
    m = _tp_psum(
        jnp.einsum(
            "btf,fd->btd",
            jax.nn.gelu(g, approximate=True) * u,
            p["w_down"].astype(dt),
        ),
        tp,
    )
    return x + norm("post_mlp_norm", m)


def _stage(
    stage_params: dict, x: jax.Array, cfg, backend: str, seg=None,
    tp: bool = False, ep: bool = False,
):
    """Run this stage's [layers_per_stage] blocks via lax.scan; returns
    (out, aux) where aux is the summed router loss of this stage's MoE
    layers (0.0 for dense families). For Gemma the scanned unit is a
    local+global PAIR (the alternation is a static per-block property,
    so it cannot ride a plain layer scan)."""
    if _is_gemma(cfg):
        out, _ = jax.lax.scan(
            _gemma_pair_body(cfg, backend, seg, tp), x, stage_params
        )
        return out, jnp.zeros((), jnp.float32)

    if _returns_aux(cfg):
        moe_blk = _mla_moe_block if _is_mla(cfg) else _mixtral_block

        def moe_body(carry, layer_p):
            h, aux = carry
            h, a = moe_blk(layer_p, h, cfg, backend, seg, tp, ep)
            return (h, aux + a.astype(jnp.float32)), None

        (out, aux), _ = jax.lax.scan(
            moe_body, (x, jnp.zeros((), jnp.float32)), stage_params
        )
        return out, aux

    blk = _mla_block if _is_mla(cfg) else _block

    def body(h, layer_p):
        return blk(layer_p, h, cfg, backend, seg, tp), None

    out, _ = jax.lax.scan(body, x, stage_params)
    return out, jnp.zeros((), jnp.float32)


# ----------------------------------------------------------------------
# GPipe schedule
# ----------------------------------------------------------------------


def _gpipe_local(stage_params, x_mb, *seg_mb, cfg, backend):
    """Per-device body (inside shard_map): stream M microbatches through
    the pipe ring. x_mb: [M, mb_local, T, D]; seg_mb is () or one
    [M, mb_local, T] int32 array of segment ids. Returns (outs, aux):
    outs in x_mb's shape (valid data produced on the last stage, zeros
    elsewhere, psum-combined); aux the global-mean router loss scalar
    (0.0 for dense families), replicated on every device."""
    s = axis_size(AXIS_PIPE)
    sidx = jax.lax.axis_index(AXIS_PIPE)
    # Static (trace-time) tensor/expert-parallel degrees: the stage
    # weights' head/ffn/expert axes arrive pre-sharded per
    # _TENSOR_LEAF_AXIS / _EXPERT_LEAVES.
    tp = axis_size(AXIS_TENSOR) > 1
    ep = axis_size(AXIS_EXPERT) > 1
    # Local leading stage dim is 1 after sharding: drop it.
    stage_params = jax.tree.map(lambda a: a[0], stage_params)
    m = x_mb.shape[0]
    perm = [(i, (i + 1) % s) for i in range(s)]
    has_seg = bool(seg_mb)
    seg_all = seg_mb[0] if has_seg else None

    def tick(carry, t):
        recv, outs, aux_acc = carry
        x_in = jnp.where(sidx == 0, x_mb[jnp.clip(t, 0, m - 1)], recv)
        if has_seg:
            # Stage sidx processes microbatch t - sidx at tick t (the
            # same invariant the output collection uses). seg_all is
            # replicated across the pipe axis (its spec doesn't mention
            # pipe), so the ids are indexed locally — no need to
            # ppermute them around the ring with the activations.
            seg_in = seg_all[jnp.clip(t - sidx, 0, m - 1)]
        else:
            seg_in = None
        out, aux = _stage(stage_params, x_in, cfg, backend, seg_in, tp, ep)
        nxt = jax.lax.ppermute(out, AXIS_PIPE, perm)
        # Last stage finishes microbatch t-(s-1) at tick t.
        oidx = jnp.clip(t - (s - 1), 0, m - 1)
        valid = (t >= s - 1) & (sidx == s - 1)
        cur = jax.lax.dynamic_index_in_dim(outs, oidx, 0, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, out, cur), oidx, 0
        )
        # Bubble ticks run the stage on clip-duplicated (garbage)
        # microbatches; only ticks where stage sidx holds a REAL
        # microbatch (t - sidx in [0, m)) contribute router loss.
        real = (t >= sidx) & (t < sidx + m)
        aux_acc = aux_acc + jnp.where(real, aux, 0.0)
        return (nxt, outs, aux_acc), None

    zeros = jnp.zeros_like(x_mb[0])
    outs0 = jnp.zeros_like(x_mb)
    # aux rides through the body as shape (1,), never (): jax 0.4.x
    # shard_map autodiff gives residuals the {0: all_axes} out-spec,
    # which is unsatisfiable for a rank-0 residual and raises
    # _SpecError from the transpose. Callers take [0] outside.
    (_, outs, aux_sum), _ = jax.lax.scan(
        tick, (zeros, outs0, jnp.zeros((1,), jnp.float32)),
        jnp.arange(m + s - 1),
    )
    # Non-last stages hold zeros; the psum replicates the real result
    # across the pipe axis (required: `pipe` is unmentioned in out_specs).
    outs = jax.lax.psum(outs, AXIS_PIPE)
    # aux: sum over stages (pipe) = sum over all layers; mean over the
    # m x (data x fsdp shards) routing groups. tensor/expert ranks
    # compute identical copies (router is replicated), so they are NOT
    # psum axes — the result is already replicated across them.
    dp = axis_size(AXIS_DATA) * axis_size(AXIS_FSDP)
    aux = jax.lax.psum(
        aux_sum, (AXIS_PIPE, AXIS_DATA, AXIS_FSDP)
    ) / float(m * dp)
    return outs, aux


def pipeline_forward(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    pipe: PipelineConfig,
    mesh: Mesh,
    backend: Optional[str] = None,
    segment_ids: Optional[jax.Array] = None,
    return_hidden: bool = False,
) -> jax.Array:
    """Full LM forward with the block stack pipelined: logits [B, T, V]
    (or, with ``return_hidden``, the post-final-norm hidden states
    [B, T, D] for the chunked-vocab CE path, which applies the head
    per sequence chunk and never materializes full logits). For MoE
    configs the return value is a TUPLE (logits_or_hidden, aux): the
    mean router loss (already /n_layers, matching the flax Mixtral
    convention) that the training objective must add.

    Embedding and the head run outside the pipeline region (they are a
    small fraction of compute and live replicated / batch-sharded);
    everything between — the whole layer stack — runs on the pipe ring.
    ``segment_ids`` [B, T] masks cross-document attention for packed
    batches; ids ride the ring with their microbatch's activations.
    """
    is_moe = _returns_aux(cfg)
    if mesh.shape["sequence"] != 1:
        raise NotImplementedError(
            "pipeline composes with data/fsdp/tensor/expert only for "
            f"now; mesh axis sequence has size {mesh.shape['sequence']}"
        )
    ep = mesh.shape[AXIS_EXPERT]
    if ep > 1:
        if not is_moe:
            raise NotImplementedError(
                f"mesh expert axis has size {ep} but {type(cfg).__name__}"
                " has no experts to shard over it"
            )
        if cfg.n_experts % ep:
            raise ValueError(
                f"mesh expert={ep} must divide n_experts="
                f"{cfg.n_experts} for pipelined expert parallelism"
            )
    tp = mesh.shape[AXIS_TENSOR]
    if tp > 1:
        # Megatron split: heads over q/k/v/o, ffn width over
        # gate/up/down. Uneven splits would silently mis-shard the
        # stacked weights. MLA has no kv heads (one shared latent,
        # replicated kernels); MLA-MoE shards moe_d_ff (routed stacks)
        # and the shared-expert width, never the dense d_ff (those
        # leaves don't exist in its stacks).
        checks = [("n_heads", cfg.n_heads)]
        if _is_mla(cfg) and cfg.moe:
            # moe_d_ff % tp also covers the shared-expert width
            # (n_shared * moe_d_ff) — no separate check needed.
            checks.append(("moe_d_ff", cfg.moe_d_ff))
        else:
            checks.append(("d_ff", cfg.d_ff))
        if not _is_mla(cfg):
            checks.append(("n_kv_heads", cfg.n_kv_heads))
        for fname, v in checks:
            if v % tp:
                raise ValueError(
                    f"mesh tensor={tp} must divide {fname}={v} "
                    f"for pipelined tensor parallelism"
                )
    if mesh.shape[AXIS_PIPE] != pipe.n_stages:
        # Without this, sharding a [S, ...] stack over a differently-sized
        # pipe axis silently drops (or duplicates) stages' layers.
        raise ValueError(
            f"PipelineConfig.n_stages={pipe.n_stages} but mesh pipe axis "
            f"has size {mesh.shape[AXIS_PIPE]}"
        )
    pipe.validate(cfg, tokens.shape[0])
    backend = backend or cfg.attention_backend
    b, t = tokens.shape
    m = pipe.n_microbatches
    dp = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
    if (b // m) % dp:
        raise ValueError(
            f"microbatch rows {b // m} (batch {b} / {m} microbatches) "
            f"not divisible over data x fsdp = {dp} devices"
        )

    x = _embed(params, tokens, cfg)  # [B, T, D]
    x = x.reshape(m, b // m, t, cfg.d_model)

    mb_spec = P(None, (AXIS_DATA, AXIS_FSDP), None, None)
    stage_specs = stage_partition_specs(params["stages"])
    local = partial(_gpipe_local, cfg=cfg, backend=backend)
    if segment_ids is None:
        hidden, aux = shard_map(
            local,
            mesh=mesh,
            in_specs=(stage_specs, mb_spec),
            out_specs=(mb_spec, P()),
            check_vma=False,
        )(params["stages"], x)
    else:
        seg = segment_ids.astype(jnp.int32).reshape(m, b // m, t)
        seg_spec = P(None, (AXIS_DATA, AXIS_FSDP), None)
        hidden, aux = shard_map(
            local,
            mesh=mesh,
            in_specs=(stage_specs, mb_spec, seg_spec),
            out_specs=(mb_spec, P()),
            check_vma=False,
        )(params["stages"], x, seg)
    hidden = hidden.reshape(b, t, cfg.d_model)

    out = (
        _final_norm(params, hidden, cfg)
        if return_hidden
        else _logits_epilogue(params, hidden, cfg)
    )
    if is_moe:
        return out, aux[0] / cfg.n_layers
    return out


def _head_kernel(params: dict) -> jax.Array:
    """[D, V] LM head: dedicated, or the transposed tied embedding."""
    return (
        params["head"] if "head" in params else params["embed"].T
    )


def _embed(params: dict, tokens: jax.Array, cfg) -> jax.Array:
    """Token embedding lookup incl. Gemma's sqrt(d) scaling — ONE copy
    for the pipelined and sequential forwards."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    if _is_gemma(cfg):
        x = x * jnp.asarray(
            math.sqrt(cfg.d_model), cfg.dtype
        ).astype(x.dtype)
    return x


def _final_norm(params: dict, hidden: jax.Array, cfg) -> jax.Array:
    """Final RMSNorm incl. Gemma's (1+w) offset — ONE copy for the
    logits epilogue and the return_hidden (chunked-CE) path."""
    fnorm = params["final_norm"]
    if _is_gemma(cfg):
        fnorm = fnorm + 1.0
    return rms_norm(hidden, fnorm, cfg.rms_eps)


def _logits_epilogue(params: dict, hidden: jax.Array, cfg) -> jax.Array:
    """final norm -> head -> optional soft-cap: ONE copy shared by the
    pipelined and sequential (parity-oracle) forwards."""
    h = _final_norm(params, hidden, cfg)
    logits = h.astype(jnp.float32) @ _head_kernel(params).astype(
        jnp.float32
    )
    cap = getattr(cfg, "final_logit_soft_cap", None)
    if cap is not None:
        from tpufw.ops.attention import tanh_soft_cap

        logits = tanh_soft_cap(logits, cap)
    return logits


def _gemma_pair_body(cfg, backend, seg, tp: bool = False):
    """The scanned local+global pair: ONE copy for the staged schedule
    and the sequential oracle."""

    def body(h, pair_p):
        h = _gemma_block(
            pair_p["local"], h, cfg, backend, seg, cfg.sliding_window, tp
        )
        h = _gemma_block(
            pair_p["global"], h, cfg, backend, seg, None, tp
        )
        return h, None

    return body


def reference_forward(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    backend: str = "xla",
    segment_ids: Optional[jax.Array] = None,
    group_rows: Optional[int] = None,
) -> jax.Array:
    """Sequential evaluation of the SAME params (no pipe axis) — the
    parity oracle for the schedule.

    For MoE configs, routing capacity is a per-group property: the
    schedule routes each (microbatch x data-shard) group of
    ``group_rows`` rows independently, so the oracle must group the
    same way to be bit-comparable (vmap over row groups). Returns
    (logits, aux) for MoE — aux meaned over groups, summed over
    layers, /n_layers — matching ``pipeline_forward``'s accounting.
    """
    b, t = tokens.shape
    x = _embed(params, tokens, cfg)
    flat = jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), params["stages"]
    )
    seg = (
        None if segment_ids is None else segment_ids.astype(jnp.int32)
    )

    if _returns_aux(cfg):
        gr = group_rows or b
        if b % gr:
            raise ValueError(f"batch {b} not divisible by group_rows {gr}")
        moe_blk = _mla_moe_block if _is_mla(cfg) else _mixtral_block

        def run_group(xg, sg):
            def body(carry, layer_p):
                h, aux = carry
                h, a = moe_blk(layer_p, h, cfg, backend, sg)
                return (h, aux + a.astype(jnp.float32)), None

            (h, aux), _ = jax.lax.scan(
                body, (xg, jnp.zeros((), jnp.float32)), flat
            )
            return h, aux

        xg = x.reshape(b // gr, gr, t, cfg.d_model)
        if seg is None:
            hidden, aux = jax.vmap(lambda xx: run_group(xx, None))(xg)
        else:
            hidden, aux = jax.vmap(run_group)(
                xg, seg.reshape(b // gr, gr, t)
            )
        hidden = hidden.reshape(b, t, cfg.d_model)
        return (
            _logits_epilogue(params, hidden, cfg),
            jnp.mean(aux) / cfg.n_layers,
        )

    if _is_gemma(cfg):
        body = _gemma_pair_body(cfg, backend, seg)
    else:
        blk = _mla_block if _is_mla(cfg) else _block

        def body(h, layer_p):
            return blk(layer_p, h, cfg, backend, seg), None

    x, _ = jax.lax.scan(body, x, flat)
    return _logits_epilogue(params, x, cfg)


def pipeline_loss(
    params: dict,
    batch: dict | jax.Array,
    cfg: LlamaConfig,
    pipe: PipelineConfig,
    mesh: Mesh,
    loss_chunk_size: Optional[int] = None,
    loss_chunk_dtype=None,
) -> jax.Array:
    """LM objective through the pipelined forward — the SAME shift +
    packed-batch masking as the flax trainer (shift_and_mask), so the
    two training paths can't diverge on what they optimize. ``batch``
    is {tokens [+ segment_ids, loss_mask]} (a bare token array is
    wrapped for back-compat)."""
    return pipeline_eval(
        params, batch, cfg, pipe, mesh, loss_chunk_size, loss_chunk_dtype
    )["loss"]


def pipeline_eval(
    params: dict,
    batch: dict | jax.Array,
    cfg: LlamaConfig,
    pipe: PipelineConfig,
    mesh: Mesh,
    loss_chunk_size: Optional[int] = None,
    loss_chunk_dtype=None,
) -> dict:
    """Forward-only objective through the pipelined model:
    {loss, n_tokens} — the held-out-eval analog of ``pipeline_loss``
    (same shift/mask, no gradient), so PipelineTrainer.evaluate reports
    numbers directly comparable to the flax Trainer's. With
    ``loss_chunk_size`` the head runs inside the chunked-vocab CE
    (tpufw.ops.loss) and [B, T, V] logits never materialize."""
    from tpufw.train.trainer import cross_entropy_loss, shift_and_mask

    if not isinstance(batch, dict):
        batch = {"tokens": batch}
    inputs, targets, seg_in, mask = shift_and_mask(batch)
    aux = 0.0  # MoE router loss joins the objective, as in the flax path
    if loss_chunk_size:
        from tpufw.ops.loss import chunked_cross_entropy

        hidden = pipeline_forward(
            params, inputs, cfg, pipe, mesh, segment_ids=seg_in,
            return_hidden=True,
        )
        if _returns_aux(cfg):
            hidden, aux = hidden
        loss, n = chunked_cross_entropy(
            hidden, _head_kernel(params), targets, mask,
            chunk_size=loss_chunk_size,
            compute_dtype=loss_chunk_dtype or jnp.bfloat16,
            logits_soft_cap=getattr(cfg, "final_logit_soft_cap", None),
        )
        return {"loss": loss + aux, "n_tokens": n}
    logits = pipeline_forward(
        params, inputs, cfg, pipe, mesh, segment_ids=seg_in
    )
    if _returns_aux(cfg):
        logits, aux = logits
    loss, n = cross_entropy_loss(logits, targets, mask)
    return {"loss": loss + aux, "n_tokens": n}


def pipeline_train_step(
    params: dict,
    opt_state: Any,
    tokens: jax.Array,
    tx,
    cfg: LlamaConfig,
    pipe: PipelineConfig,
    mesh: Mesh,
) -> tuple[dict, Any, jax.Array]:
    """One SGD/AdamW step over the pipelined model (jit this)."""
    import optax

    loss, grads = jax.value_and_grad(pipeline_loss)(
        params, tokens, cfg, pipe, mesh
    )
    updates, opt_state = tx.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss
