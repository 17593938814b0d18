"""Analytic per-device HBM estimate for a training or decode config.

The round-2/3 bench sweeps found the winning batch/remat point by
OOM-ladder trial on hardware (docs/PERF.md); this tool is the
paper-napkin version users run FIRST: params + optimizer + gradient +
activation (per remat policy) + logits/CE + KV-cache bytes, divided
over the mesh the way tpufw actually shards them, against the chip's
usable HBM. Estimates are first-order (XLA fusion/padding/temp buffers
add real variance) — the point is choosing a starting batch size and
remat policy, not replacing the measured ladder.

    python -m tpufw.tools.estimate_memory --model llama3_8b \
        --batch 16 --seq 2048 --fsdp 16
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Optional


def _bytes(dtype) -> int:
    """Itemsize for numpy/jax dtypes AND their string names (ml_dtypes
    registers bfloat16 with numpy, so np.dtype handles all of them)."""
    import numpy as np

    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        import jax.numpy as jnp

        return jnp.dtype(dtype).itemsize


def _attn_geometry(cfg) -> tuple[float, float]:
    """(per-token attention projection terms, cached floats per token).

    MHA/GQA (Llama-family): q + o-input (H*dh each) + k + v (K*dh
    each); cache = 2 * K * dh. MLA (DeepSeek): q [H*(dn+dr)], the
    packed latent [kvr+dr], the expanded k/v [H*(dn+dv)], o-input
    [H*dv]; cache = the LATENT kvr + dr — the 3.6x-smaller figure that
    is the family's point (tpufw.models.deepseek)."""
    if hasattr(cfg, "kv_lora_rank"):
        h = cfg.n_heads
        dn, dr, dv = (
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        )
        terms = (
            h * (dn + dr)          # q
            + cfg.kv_lora_rank + dr  # packed latent
            + h * (dn + dv)        # expanded k_nope + v
            + h * dv               # o input
        )
        if getattr(cfg, "q_lora_rank", None):
            terms += cfg.q_lora_rank
        return float(terms), float(cfg.kv_lora_rank + dr)
    h_dh = cfg.n_heads * cfg.head_dim
    kv_dh = cfg.n_kv_heads * cfg.head_dim
    return float(2 * h_dh + 2 * kv_dh), float(2 * kv_dh)


@dataclasses.dataclass(frozen=True)
class MemoryEstimate:
    """Per-device byte totals (floats are bytes; names say what)."""

    params: float
    optimizer: float
    gradients: float
    activations: float
    logits_ce: float
    kv_cache: float

    def total(self) -> float:
        return (
            self.params + self.optimizer + self.gradients
            + self.activations + self.logits_ce + self.kv_cache
        )

    def as_dict(self) -> dict:
        d = {k: round(v / 2**30, 3) for k, v in dataclasses.asdict(self).items()}
        d["total_gib"] = round(self.total() / 2**30, 3)
        return d


def estimate_train(
    cfg,
    batch_size: int,
    seq_len: int,
    n_shards: int = 1,
    remat_policy: Optional[str] = None,
    loss_chunk_size: Optional[int] = None,
    adam_mu_dtype: Optional[str] = None,
    grad_accum: int = 1,
) -> MemoryEstimate:
    """Training-step footprint per device — the programmatic entry point
    (the autotuner's HBM pruning oracle, tpufw.tune.space); the CLI below
    is a thin JSON printer over it.

    ``n_shards`` is the param/optimizer sharding degree (the ``fsdp``
    axis; ZeRO-3 layout — tpufw/mesh). The batch dim is assumed sharded
    over the same data x fsdp product, so activation rows divide by it
    too. ``grad_accum`` > 1 further divides activation/logits rows by
    the microbatch count: each microbatch's fwd+bwd completes inside the
    accumulation scan, so only one microbatch's activations are live
    (tpufw.train.trainer.train_step) — at the cost of one extra fp32
    gradient accumulator tree. Mirrors the trainer's actual layout:

    - params in ``cfg.param_dtype``, sharded over fsdp;
    - AdamW mu (``adam_mu_dtype`` or fp32) + nu (fp32), sharded;
    - one full gradient tree materialized between bwd and the update
      (param_dtype), sharded;
    - activations: scan-over-layers saves the per-layer block INPUT
      [B, T, D] in cfg.dtype (all policies), plus per-layer residents
      by policy — "dots" adds the projection outputs (q/k/v/o
      [B,T,H*dh] x4 and gate/up [B,T,f] x2 + down input [B,T,f]),
      "everything" ~2x that, "nothing" adds only one transient block's
      worth;
    - logits/CE: chunked CE holds [B, chunk, V] fp32 (+ bwd double);
      full logits hold [B, T-1, V].
    """
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    p_bytes = _bytes(cfg.param_dtype)
    a_bytes = _bytes(cfg.dtype)
    n_params = cfg.n_params()
    params = n_params * p_bytes / n_shards
    mu_bytes = _bytes(adam_mu_dtype or "float32")
    optimizer = n_params * (mu_bytes + 4) / n_shards
    gradients = n_params * p_bytes / n_shards
    if grad_accum > 1:
        # The accumulation scan carries a full fp32 gradient tree next
        # to each microbatch's own gradients (train_step's zero_g).
        gradients += n_params * 4 / n_shards

    rows = batch_size / max(n_shards, 1) / grad_accum
    t = seq_len
    d, f, l = cfg.d_model, cfg.d_ff, cfg.n_layers
    attn_terms, _ = _attn_geometry(cfg)
    policy = remat_policy or getattr(cfg, "remat_policy", "dots")

    boundary = l * rows * t * d * a_bytes  # saved scan carries
    g_tokens = rows * t
    mlp_terms = 3 * f  # gate, up, down-input (dense MLP)
    moe_terms = 0.0
    if getattr(cfg, "n_experts", 0):
        # Einsum-dispatch MoE (tpufw.models.mixtral): the expert
        # buffers replace the dense MLP — xe [E,C,d] + gate/up
        # [E,C,f] x2 with E*C = capacity_factor * G * k tokens-worth —
        # and the dispatch/combine tensors are [G, E, C] =
        # cf * k * G^2 elements EACH, the quadratic-in-group-size term
        # that dominates at large per-device batch (the reason MoE
        # configs shard the routing group hard).
        k = cfg.experts_per_token
        cf = cfg.capacity_factor
        # DeepSeek's fine-grained experts are moe_d_ff wide (and its
        # shared experts add a dense n_shared * moe_d_ff MLP).
        f_e = getattr(cfg, "moe_d_ff", f)
        mlp_terms = cf * k * (d + 2 * f_e)
        n_shared = getattr(cfg, "n_shared_experts", 0)
        if n_shared:
            mlp_terms += 3 * n_shared * f_e
        moe_terms = 2 * cf * k * g_tokens  # dispatch+combine, per token
    per_layer_dots = g_tokens * (
        attn_terms            # projection outputs (arch-specific)
        + mlp_terms
        + moe_terms
        + 2 * d               # two norm outputs
    ) * a_bytes
    if policy == "nothing":
        live = per_layer_dots  # one block recomputed at a time
    elif policy == "attn_out":
        # "nothing" plus one saved [rows, T, D] attention output per
        # layer (tpufw.models.llama _REMAT_POLICIES).
        live = per_layer_dots + l * g_tokens * d * a_bytes
    elif policy == "dots":
        live = l * per_layer_dots
    elif policy == "everything":
        # Attention internals too (scores dominate).
        live = l * (
            per_layer_dots
            + rows * cfg.n_heads * t * t * a_bytes
        )
    else:
        raise ValueError(
            f"unknown remat_policy {policy!r}; choose from "
            "dots|nothing|attn_out|everything"
        )
    activations = boundary + live

    v = cfg.vocab_size
    if loss_chunk_size:
        logits_ce = 2 * rows * min(loss_chunk_size, t) * v * 4
    else:
        logits_ce = 2 * rows * (t - 1) * v * 4

    return MemoryEstimate(
        params=params,
        optimizer=optimizer,
        gradients=gradients,
        activations=activations,
        logits_ce=logits_ce,
        kv_cache=0.0,
    )


def estimate_decode(
    cfg,
    batch_size: int,
    cache_len: Optional[int] = None,
    weights_dtype: Optional[str] = None,
    n_shards: int = 1,
) -> MemoryEstimate:
    """Serving footprint per device: weights (cast per
    ``weights_dtype`` — the TPUFW_DECODE_DTYPE lever) + the KV cache
    [B, cache_len] in cfg.dtype across every layer. ``n_shards``
    divides both (sharded-params decode shards weights over fsdp and
    batch rows over the same devices)."""
    w_bytes = _bytes(weights_dtype or cfg.param_dtype)
    a_bytes = _bytes(cfg.dtype)
    s = cache_len or cfg.max_seq_len
    _, kv_per_token = _attn_geometry(cfg)
    kv = cfg.n_layers * batch_size * s * kv_per_token * a_bytes
    return MemoryEstimate(
        params=cfg.n_params() * w_bytes / n_shards,
        optimizer=0.0,
        gradients=0.0,
        activations=0.0,
        logits_ce=batch_size * cfg.vocab_size * 4 / n_shards,
        kv_cache=kv / n_shards,
    )


def main(argv=None) -> int:
    from tpufw.models import (
        DEEPSEEK_CONFIGS,
        GEMMA_CONFIGS,
        LLAMA_CONFIGS,
        MIXTRAL_CONFIGS,
    )

    from tpufw.configs import bench_model_config

    presets = {
        **LLAMA_CONFIGS,
        **MIXTRAL_CONFIGS,
        **GEMMA_CONFIGS,
        **DEEPSEEK_CONFIGS,
        # The bench's own headline config — this tool's stated purpose
        # is picking its batch/remat point before the OOM ladder does.
        "llama3_600m_bench": bench_model_config(),
    }
    ap = argparse.ArgumentParser(
        description="Analytic per-device HBM estimate (training or decode)"
    )
    ap.add_argument("--model", required=True, help=f"one of {sorted(presets)}")
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--seq", type=int, default=None, help="train seq len")
    ap.add_argument("--fsdp", type=int, default=1, help="param shards")
    ap.add_argument(
        "--remat", default=None,
        choices=["dots", "nothing", "everything"],
    )
    ap.add_argument("--ce-chunk", type=int, default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--adam-mu-dtype", default=None)
    ap.add_argument(
        "--decode", action="store_true",
        help="serving estimate instead of training",
    )
    ap.add_argument("--cache-len", type=int, default=None)
    ap.add_argument(
        "--decode-dtype", default=None,
        help="weights dtype at decode (TPUFW_DECODE_DTYPE)",
    )
    ap.add_argument(
        "--chip", default="v5e",
        help="chip spec to compare against (static table; 'auto' "
        "asks the live backend, which must be an accelerator)",
    )
    args = ap.parse_args(argv)
    if args.model not in presets:
        ap.error(f"unknown --model {args.model!r}")
    cfg = presets[args.model]
    from tpufw.utils.hardware import CHIP_SPECS

    if args.chip != "auto" and args.chip not in CHIP_SPECS:
        ap.error(
            f"unknown --chip {args.chip!r}; choose from "
            f"{sorted(CHIP_SPECS)} or 'auto'"
        )

    if args.decode:
        est = estimate_decode(
            cfg, args.batch, args.cache_len, args.decode_dtype,
            n_shards=args.fsdp,
        )
    else:
        est = estimate_train(
            cfg,
            args.batch,
            args.seq or cfg.max_seq_len,
            n_shards=args.fsdp,
            remat_policy=args.remat,
            loss_chunk_size=args.ce_chunk,
            adam_mu_dtype=args.adam_mu_dtype,
            grad_accum=args.grad_accum,
        )
    from tpufw.utils.hardware import detect_chip

    # Static chip table by default: the estimate is pure arithmetic and
    # must not block on (or require) a live accelerator backend.
    chip = (
        detect_chip() if args.chip == "auto" else CHIP_SPECS[args.chip]
    )
    if chip is None:
        ap.error("--chip auto found a CPU backend; name a chip instead")
    out = {
        "model": args.model,
        "mode": "decode" if args.decode else "train",
        **est.as_dict(),
        "chip": chip.name,
        "chip_hbm_gib": round(chip.hbm_bytes / 2**30, 1),
        "fits": est.total() < chip.hbm_bytes,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
