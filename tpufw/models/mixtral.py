"""Mixtral-8x7B MoE family — BASELINE config 5 (expert parallel, stretch).

Sparse mixture-of-experts with top-k routing, built the GSPMD way: routing
is pure einsum algebra over a capacity-bounded dispatch tensor, expert
weights carry an ``expert`` logical axis that tpufw.mesh maps onto the
``expert`` mesh axis, and XLA's partitioner emits the all-to-alls. No
per-expert Python loops, no send/recv — the dispatch einsum IS the
communication, which is exactly how expert parallelism should look on an
ICI-connected TPU mesh (vs. the NCCL alltoall wiring a GPU MoE stack
hand-rolls; the reference itself has no parallelism at all, SURVEY.md §2c).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import ad_checkpoint
from flax import linen as nn

from tpufw.models.llama import (
    Attention,
    LlamaConfig,
    RMSNorm,
    decoder_lm,
    reject_quant_lora,
)
from tpufw.ops import moe_live
from tpufw.ops.moe import (
    expert_capacity,
    route_topk_capacity,
    sorted_route,
)


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    n_experts: int = 8
    experts_per_token: int = 2
    # Per-expert buffer = capacity_factor * (tokens * k / n_experts).
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.02
    router_z_weight: float = 1e-3
    # "einsum": one-hot dispatch/combine contractions — the tensors ARE
    # the communication when the expert axis is sharded (EP). "sorted":
    # token-sorted grouped matmuls via jax.lax.ragged_dot — O(k*G*d)
    # gather/scatter instead of O(G*E*C*d) one-hot FLOPs (measured 5x
    # the expert compute at bench scale, docs/PERF.md) — for
    # single-device or data-sharded training where experts stay whole.
    moe_dispatch: str = "einsum"

    def n_params(self, include_embed: bool = True) -> int:
        d, l = self.d_model, self.n_layers
        attn = l * (
            d * self.n_heads * self.head_dim
            + 2 * d * self.n_kv_heads * self.head_dim
            + self.n_heads * self.head_dim * d
        )
        moe = l * (3 * d * self.d_ff * self.n_experts + d * self.n_experts)
        norms = (2 * l + 1) * d
        total = attn + moe + norms
        if include_embed:
            total += self.vocab_size * d
            if not self.tie_embeddings:
                total += d * self.vocab_size
        return total

    def flops_per_token(self, seq_len: int) -> float:
        """Active-parameter FLOPs: only k experts run per token."""
        d, l, k = self.d_model, self.n_layers, self.experts_per_token
        n_active = (
            l
            * (
                d * self.n_heads * self.head_dim
                + 2 * d * self.n_kv_heads * self.head_dim
                + self.n_heads * self.head_dim * d
                + 3 * d * self.d_ff * k
                + d * self.n_experts
            )
            + d * self.vocab_size
        )
        return 6.0 * n_active + self._attn_score_flops(seq_len)


MIXTRAL_CONFIGS: dict[str, MixtralConfig] = {
    "mixtral_8x7b": MixtralConfig(
        vocab_size=32_000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        d_ff=14_336,
        rope_theta=1e6,
        max_seq_len=32_768,
        n_experts=8,
        experts_per_token=2,
        attention_backend="flash",
    ),
    "mixtral_tiny": MixtralConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        max_seq_len=128,
        n_experts=4,
        experts_per_token=2,
        remat=False,
    ),
}


class QuantExpertKernel(nn.Module):
    """int8 expert-stacked kernel [E, in, out] + per-(expert,
    out-channel) fp32 scale — the MoE serving twin of
    ``llama.QuantDenseGeneral``. Param shapes match what
    ``tpufw.ops.quant.quantize_params`` emits for the raw expert
    stacks; logical axes mirror the fp weights so sharded serving lays
    out identically (expert axis stays on the ``expert`` mesh axis)."""

    shape: tuple  # (E, d_in, d_out)
    names: tuple  # logical axes of the fp kernel
    dtype: Any

    @nn.compact
    def __call__(self, xe: jax.Array) -> jax.Array:
        e, _, d_out = self.shape
        q = self.param(
            "q_kernel",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), self.names
            ),
            self.shape,
            jnp.int8,
        )
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(
                nn.initializers.ones_init(), (self.names[0], self.names[2])
            ),
            (e, d_out),
            jnp.float32,
        )
        y = jnp.einsum("eci,eio->eco", xe, q.astype(self.dtype))
        return y * scale[:, None, :].astype(y.dtype)


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU experts with capacity-bounded einsum dispatch.

    Returns (y, aux_loss): aux = load-balance loss (Switch-style fraction *
    probability product) + router z-loss, pre-weighted by the config.

    ``d_ff`` overrides the per-expert width (DeepSeek's fine-grained
    experts are narrower than the dense cfg.d_ff); ``norm_topk=False``
    keeps raw softmax combine weights (DeepSeek-V2). The ``cfg`` only
    needs the MoE fields (n_experts, experts_per_token,
    capacity_factor, router_*_weight) plus dtypes — DeepseekConfig
    passes a compatible view.

    ``held = (first, n)``: this chip's share of an expert-parallel
    layer. The router keeps its width ``cfg.n_experts`` and scores and
    weighs over all of them; the stacks hold the n experts
    [first, first + n) and the layer returns THEIR part of the result
    (what the experts held elsewhere add is those chips' to compute;
    nothing here stands in for them or for the exchange).
    ``scoring="sigmoid"`` adds the selection bias ``router_bias`` [E]
    (tpufw.ops.moe._topk_select).
    """

    cfg: MixtralConfig
    d_ff: Optional[int] = None
    norm_topk: bool = True
    # (n_group, topk_group): DeepSeek-236B group-limited selection —
    # passed straight to tpufw.ops.moe.route_topk_capacity.
    group_limit: Optional[tuple] = None
    scoring: str = "softmax"
    held: Optional[tuple] = None

    def _held(self) -> Optional[tuple]:
        """``held``, or None where it names every expert: the whole
        layer is then the program it is without the option."""
        if self.held is None or tuple(self.held) == (0, self.cfg.n_experts):
            return None
        return tuple(self.held)

    def _n_held(self) -> int:
        """Length of the expert stacks here."""
        held = self._held()
        return self.cfg.n_experts if held is None else held[1]

    def _routing(self) -> dict:
        """The keyword arguments both routing functions share."""
        kw = dict(norm_topk=self.norm_topk, group_limit=self.group_limit)
        if self._held() is not None:
            kw["held"] = self._held()
        if self.scoring != "softmax":
            kw["scoring"] = self.scoring
            kw["select_bias"] = self.param(
                "router_bias",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("expert",)
                ),
                (self.cfg.n_experts,),
                jnp.float32,
            )
        return kw

    def _expert_matmul(
        self, name: str, xe: jax.Array, shape: tuple, names: tuple
    ) -> jax.Array:
        """One expert-stacked contraction [E,C,in] @ [E,in,out] ->
        [E,C,out], through whichever weight form the config declares:

        - fp kernel (training default), with optional per-expert LoRA
          (``cfg.lora_rank``): A [E,in,r] fan-in init, B [E,r,out] zero
          init — step 0 equals the base model, exactly like the shared
          ``lora_delta`` on attention projections. Params land as
          ``{name}_lora_a/b`` RAW-array siblings of the base stack
          (models/lora.py merges both layouts).
        - int8 + per-(expert, out-channel) scale for serving
          (``cfg.quantized_weights``; shapes match ``quantize_params``).
        """
        cfg = self.cfg
        if getattr(cfg, "quantized_weights", False):
            reject_quant_lora(cfg)
            sub = QuantExpertKernel(
                shape=shape, names=names, dtype=cfg.dtype, name=name
            )
            return sub(xe)
        w, a, bw = self._expert_weights(name, shape, names)
        y = jnp.einsum("eci,eio->eco", xe, w.astype(cfg.dtype))
        if a is not None:
            lo = jnp.einsum("eci,eir->ecr", xe, a.astype(cfg.dtype))
            y = y + jnp.einsum(
                "ecr,ero->eco", lo, bw.astype(cfg.dtype)
            ) * (
                getattr(cfg, "lora_alpha", 16.0)
                / getattr(cfg, "lora_rank", 0)
            )
        return y

    def _expert_weights(self, name: str, shape: tuple, names: tuple):
        """The fp expert weight stack (+ optional LoRA pair) — ONE
        param-creation site shared by the einsum and sorted dispatch
        paths, so both produce identical checkpoints."""
        cfg = self.cfg
        e, d_in, d_out = shape
        w = self.param(
            name,
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), names
            ),
            shape,
            cfg.param_dtype,
        )
        a = bw = None
        r = getattr(cfg, "lora_rank", 0)
        if r:
            a = self.param(
                f"{name}_lora_a",
                nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(),
                    (names[0], names[1], "lora"),
                ),
                (e, d_in, r),
                cfg.param_dtype,
            )
            bw = self.param(
                f"{name}_lora_b",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(),
                    (names[0], "lora", names[2]),
                ),
                (e, r, d_out),
                cfg.param_dtype,
            )
        return w, a, bw

    def _sorted_experts(self, x, router_logits, capacity, valid, d_ff):
        """Sorted-dispatch expert compute: gather tokens into expert
        order and run grouped matmuls (``jax.lax.ragged_dot``) instead
        of contracting one-hot [G, E, C] dispatch tensors. The one-hot
        einsums cost O(G*E*C*d) FLOPs — measured 5x the expert matmuls
        themselves at bench scale, capping MoE training at 10% MFU on
        the v5e chip (docs/PERF.md) — while this path's gather/scatter
        moves O(k*G*d) bytes. Semantics (selection, capacity drops,
        aux losses) are pinned identical to the einsum path by
        ``tests/test_moe_sorted.py``.

        The stacks go to ragged_dot as they are stored, [E, in, out]:
        invalid-token rows sort last, ride in expert E-1's group and
        carry a zero gate (``route_topk_sorted``), so no zero expert
        is appended and no copy the size of a stack is made per call.

        Single-device / data-sharded only: the expert weight stacks
        stay whole. Sharding the ``expert`` mesh axis needs the einsum
        path, whose dispatch tensors ARE the all-to-all (module doc of
        tpufw.ops.moe).

        A POOL'S DECODE STEP (one token a row, ``valid`` given: some
        rows dead) runs the experts over its live rows' assignments
        alone while no more than ``moe_live.pool_rows`` rows are live
        (B/8; 0: never, and off the chip): the first ``k x R`` sorted
        assignments and their count go to ``tpufw.ops.moe_live``, which
        fetches an expert's weights only where a live assignment names
        it. Same selection, gates and precision; what is left out are
        the products a zero gate multiplied. Above R the ``ragged_dot``s
        run as everywhere else, under a ``lax.cond`` on the live count."""
        cfg = self.cfg
        b, t, d = x.shape
        k = cfg.experts_per_token
        e = self._n_held()
        g = b * t
        route = sorted_route(
            router_logits, k, capacity,
            valid=None if valid is None else valid.reshape(g),
            dtype=x.dtype,
            **self._routing(),
        )
        token, group_sizes, gates = route.token, route.group_sizes, route.gates
        xf = x.reshape(g, d).astype(cfg.dtype)
        names = (
            ("w_gate", (e, d, d_ff), ("expert", "embed", "expert_mlp")),
            ("w_up", (e, d, d_ff), ("expert", "embed", "expert_mlp")),
            ("w_down", (e, d_ff, d), ("expert", "expert_mlp", "embed")),
        )
        stacks = [self._expert_weights(*n) for n in names]

        def grouped(stack, inp):
            w, a, bw = stack
            y = jax.lax.ragged_dot(inp, w.astype(cfg.dtype), group_sizes)
            if a is not None:
                lo = jax.lax.ragged_dot(
                    inp, a.astype(cfg.dtype), group_sizes
                )
                y = y + jax.lax.ragged_dot(
                    lo, bw.astype(cfg.dtype), group_sizes
                ) * (
                    getattr(cfg, "lora_alpha", 16.0)
                    / getattr(cfg, "lora_rank", 0)
                )
            return y

        def combine(ys, token, gates):
            yw = ys * gates[:, None].astype(cfg.dtype)
            return jnp.zeros((g, d), cfg.dtype).at[token].add(yw)

        def every_row():
            xs = xf[token]  # [k*G, d]
            gate_out, up_out = grouped(stacks[0], xs), grouped(stacks[1], xs)
            h = nn.silu(gate_out) * up_out
            return combine(grouped(stacks[2], h), token, gates)

        def live_rows_alone():
            gate, up, down = (w.astype(cfg.dtype) for w, _, _ in stacks)
            at, ids = token[:n_live], route.eids[:n_live]
            n = k * g - route.counts[e]  # the sentinel's sort last
            h = moe_live.live_experts(xf[at], ids, n, gate, up)
            ys = moe_live.live_experts(h, ids, n, down)
            return combine(ys, at, gates[:n_live])

        rows = (
            moe_live.pool_rows(cfg, b, d, d_ff)
            if t == 1 and valid is not None else 0
        )
        n_live = k * rows
        if rows:
            y = jax.lax.cond(
                moe_live.takes(rows, jnp.sum(valid)),
                live_rows_alone, every_row,
            )
        else:
            y = every_row()
        return y.reshape(b, t, d), route.aux_lb, route.z

    @nn.compact
    def __call__(self, x, valid=None):
        """x: [B,T,d]; valid: optional [B,T] bool — False rows (padding in
        packed batches) are excluded from routing, capacity, and the aux
        statistics so pads can't evict real tokens from experts."""
        cfg = self.cfg
        d_ff = self.d_ff if self.d_ff is not None else cfg.d_ff
        b, t, d = x.shape
        e, k = cfg.n_experts, cfg.experts_per_token
        g = b * t
        capacity = expert_capacity(g, k, e, cfg.capacity_factor)

        router_logits = nn.DenseGeneral(
            features=e,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "expert")
            ),
            name="router",
        )(x.astype(jnp.float32))
        router_logits = router_logits.reshape(g, e)

        mode = getattr(cfg, "moe_dispatch", "einsum")
        if mode == "sorted" and getattr(cfg, "quantized_weights", False):
            # int8 expert stacks are einsum-shaped (QuantExpertKernel);
            # serving keeps the einsum path.
            mode = "einsum"
        if mode == "sorted":
            y, aux, z = self._sorted_experts(
                x, router_logits, capacity, valid, d_ff
            )
            return y, (
                cfg.router_aux_weight * aux + cfg.router_z_weight * z
            )
        if mode != "einsum":
            raise ValueError(
                f"moe_dispatch={mode!r}: choose 'einsum' (shardable "
                "over the expert axis) or 'sorted' (grouped "
                "ragged_dot, single-device/data-sharded)"
            )

        dispatch, combine, aux, z = route_topk_capacity(
            router_logits, k, capacity,
            valid=None if valid is None else valid.reshape(g),
            dtype=x.dtype,
            **self._routing(),
        )
        e = self._n_held()  # the stacks' length; the router keeps E

        xf = x.reshape(g, d)
        xe = jnp.einsum("gec,gd->ecd", dispatch, xf)  # [E, C, d]
        xe = nn.with_logical_constraint(xe, ("expert", None, "act_embed"))
        xe = xe.astype(cfg.dtype)

        gate_out = self._expert_matmul(
            "w_gate", xe, (e, d, d_ff),
            ("expert", "embed", "expert_mlp"),
        )
        up_out = self._expert_matmul(
            "w_up", xe, (e, d, d_ff),
            ("expert", "embed", "expert_mlp"),
        )
        h = nn.silu(gate_out) * up_out
        h = nn.with_logical_constraint(h, ("expert", None, "act_mlp"))
        out_e = self._expert_matmul(
            "w_down", h, (e, d_ff, d),
            ("expert", "expert_mlp", "embed"),
        )
        y = jnp.einsum("gec,ecd->gd", combine, out_e).reshape(b, t, d)

        aux_loss = (
            cfg.router_aux_weight * aux + cfg.router_z_weight * z
        )
        return y, aux_loss


class MixtralBlock(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        attn_out = Attention(
            cfg, window=getattr(cfg, "sliding_window", None), name="attn"
        )(
            RMSNorm(cfg.rms_eps, name="attn_norm")(x), positions, segment_ids
        )
        # Tag for remat_policy="attn_out" (no-op under other policies).
        x = x + ad_checkpoint.checkpoint_name(attn_out, "attn_out")
        y, aux = MoEMLP(cfg, name="moe")(
            RMSNorm(cfg.rms_eps, name="moe_norm")(x),
            valid=None if segment_ids is None else segment_ids > 0,
        )
        x = nn.with_logical_constraint(
            x + y, ("batch", "act_seq", "act_embed")
        )
        return x, aux


class Mixtral(nn.Module):
    """Decoder-only MoE LM. Returns (logits, aux_loss) when return_aux else
    logits — train_step adds aux_loss into the objective."""

    cfg: MixtralConfig

    @nn.compact
    def __call__(
        self, tokens, positions=None, segment_ids=None, return_aux=True,
        return_hidden=False,
    ):
        cfg = self.cfg
        logits, aux = decoder_lm(
            cfg, MixtralBlock, tokens, positions, segment_ids, True,
            return_hidden=return_hidden,
        )
        if return_aux:
            return logits, aux / cfg.n_layers
        return logits
