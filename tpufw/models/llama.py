"""Llama-3 model family, TPU-first (Flax linen + logical partitioning).

BASELINE configs 3-4 name Llama-3-8B as the flagship training workload; the
reference itself ships no models (its workload is ``nvidia-smi``, reference
``README.md:314``), so this implementation is additive per SURVEY.md §0.

TPU-first choices:
- bfloat16 activations, fp32 RMSNorm/softmax accumulation — keeps the MXU on
  its fast path without fp16-style loss-scale machinery.
- ``nn.scan`` over the layer stack — one compiled block body instead of
  L inlined copies; XLA compile time stays flat as L grows.
- every parameter carries *logical* axis names (``embed``, ``mlp``,
  ``q_heads``...); the (logical -> mesh) mapping lives in
  ``tpufw.mesh.logical_axis_rules`` so tp/fsdp/sp/ep layout changes never
  touch this file.
- attention is dispatched through ``tpufw.ops.multi_head_attention`` so the
  Pallas flash kernel and ring (sequence-parallel) backends drop in by
  config string.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import ad_checkpoint
from flax import linen as nn

from tpufw.ops import kv_store, multi_head_attention, paged_attend, rms_norm

Dtype = Any

# Remat (rematerialization) policies: what survives the forward pass for
# backward, vs recomputed. jax names the "no batch dims" policy after
# dot_general batch dims, which plain x@W projections don't have — so
# "dots" saves EVERY projection output, not "almost nothing".
_REMAT_POLICIES = {
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "everything": jax.checkpoint_policies.everything_saveable,
    # Save ONLY each block's attention output ([B, T, D] per layer — the
    # small tensor), recomputing everything else like "nothing" does.
    # Backward then skips re-running the flash kernel (the one fwd op
    # XLA can't fuse into its neighbours) at a memory cost of
    # n_layers * B*T*D*2 bytes, while the [B, T, d_ff] MLP
    # intermediates that make "dots" OOM still rematerialize.
    "attn_out": jax.checkpoint_policies.save_only_these_names("attn_out"),
}


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Rotary frequency transform, by ``rope_type``:

    - ``"llama3"`` (HF ``_compute_llama3_parameters``, Llama-3.1/3.3):
      low-frequency components are slowed by ``factor`` (extending the
      usable context), high-frequency components are kept, and a smooth
      ramp interpolates between the two wavelength bands.
    - ``"linear"`` (HF ``_compute_linear_scaling_parameters``, common
      on long-context Llama-2 fine-tunes): every frequency divided by
      ``factor`` — position interpolation; only ``factor`` is read.

    yarn lives on the DeepSeek family (tpufw.models.deepseek
    YarnScaling); dynamic/longrope are rejected at import
    (tools/import_hf.py) rather than silently approximated.
    """

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192
    rope_type: str = "llama3"


@dataclasses.dataclass(frozen=True)
class LayerRope:
    """One layer's rotary embedding, for a model whose layers differ in
    it (tpufw.models.laguna: YaRN over the first half of each head on
    global layers, plain rope over the whole head on window layers).
    ``scaling`` is a ``RopeScaling`` or a ``deepseek.YarnScaling``;
    ``rotary_dim`` the leading dimensions of each head that rotate
    (None: all of them; HF ``partial_rotary_factor`` x head_dim)."""

    theta: float
    scaling: Optional[Any] = None
    rotary_dim: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 14_336
    rope_theta: float = 500_000.0
    # Llama-3.1+ long-context rope transform (None = plain RoPE).
    rope_scaling: Optional[RopeScaling] = None
    rms_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    attention_backend: str = "xla"
    remat: bool = True
    # What the block remat saves for backward (tpufw.models.llama
    # _REMAT_POLICIES): "dots" saves every projection-matmul output
    # (fast bwd, memory-heavy: the [B,T,d_ff] MLP intermediates dominate
    # HBM); "nothing" recomputes the whole block from its input (full
    # remat: smallest footprint, ~1 extra fwd of FLOPs) — the standard
    # memory/compute trade, selectable per run.
    remat_policy: str = "dots"
    # False = BIDIRECTIONAL attention (LLM2Vec-style embedding
    # fine-tuning, tpufw.train.contrastive); incompatible with decode
    # (a KV cache is a causal construct).
    causal: bool = True
    scan_layers: bool = True
    # Autoregressive KV-cache mode (tpufw.infer): attention reads/writes a
    # [B, max_seq_len] cache ("cache" flax collection) instead of attending
    # within the call's own tokens. Build with cfg.decode_config().
    decode: bool = False
    # LoRA (parameter-efficient fine-tuning): rank > 0 adds frozen-base
    # low-rank adapters to every attention/MLP projection (B zero-init,
    # so step 0 equals the base model); the Trainer then updates ONLY
    # adapter params (tpufw.train.trainer lora masking), and
    # tpufw.models.lora.merge_lora folds trained adapters back into the
    # base kernels for serving/export.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # Mistral-style local attention: ONE window on EVERY layer (unlike
    # Gemma-2's alternation). None = global attention.
    sliding_window: Optional[int] = None
    # Qwen-2 style attention: biases on the q/k/v projections only
    # (o and the MLP stay bias-free). The one architectural delta
    # between Llama and the Qwen-2/2.5 family.
    attention_qkv_bias: bool = False
    # Weight-only int8 serving (tpufw.ops.quant): projection kernels are
    # stored int8 + per-output-channel scales, halving decode's HBM
    # weight traffic. Params come from quantize_params on a trained
    # tree; this flag makes the modules DECLARE the quantized form.
    # Serving-only — there is no gradient through the rounded weights.
    quantized_weights: bool = False
    # Paged KV cache (tpufw.infer.pages): kv_page > 0 replaces the
    # contiguous per-row [B, max_seq_len] KV cache with a global page
    # arena of ``kv_pages`` fixed-size pages (``kv_page`` slots each)
    # plus a per-row page table, so HBM holds pages proportional to
    # TOKENS IN FLIGHT rather than rows x max_seq_len, and matching
    # prompt prefixes share pages across rows. Decode-only (t == 1);
    # page 0 is reserved as a causally-masked junk sink. kv_quant
    # "int8" stores the paged K/V as int8 + per-token fp32 scales
    # (quantized at append, dequantized on read), halving KV bytes.
    kv_page: int = 0
    kv_pages: int = 0
    kv_quant: str = ""

    def decode_config(self) -> "LlamaConfig":
        """This architecture re-dressed for inference: KV-cache on, remat
        off (no backward pass), xla attention (flash/ring are trainers')."""
        return dataclasses.replace(
            self, decode=True, remat=False, attention_backend="xla"
        )

    def n_params(self, include_embed: bool = True) -> int:
        """Analytic parameter count (exact for this architecture)."""
        d, l = self.d_model, self.n_layers
        attn = l * (
            d * self.n_heads * self.head_dim
            + 2 * d * self.n_kv_heads * self.head_dim
            + self.n_heads * self.head_dim * d
        )
        if self.attention_qkv_bias:
            attn += l * (
                self.n_heads * self.head_dim
                + 2 * self.n_kv_heads * self.head_dim
            )
        mlp = l * 3 * d * self.d_ff
        norms = (2 * l + 1) * d
        embed = self.vocab_size * d
        head = 0 if self.tie_embeddings else d * self.vocab_size
        total = attn + mlp + norms
        if include_embed:
            total += embed + head
        return total

    def flops_per_token(self, seq_len: int) -> float:
        """Training FLOPs per token: 6*N_matmul + 6*L*d_model*T (causal).

        6*N covers fwd (2N) + bwd (4N) for all matmul params incl. the LM
        head but not the embedding gather; the attention term is the
        QK^T/AV score FLOPs, causal-halved, x3 for fwd+bwd.
        """
        d, l = self.d_model, self.n_layers
        n_matmul = (
            l
            * (
                d * self.n_heads * self.head_dim
                + 2 * d * self.n_kv_heads * self.head_dim
                + self.n_heads * self.head_dim * d
                + 3 * d * self.d_ff
            )
            + d * self.vocab_size
        )
        return 6.0 * n_matmul + self._attn_score_flops(seq_len)

    def _attn_score_flops(self, seq_len: int) -> float:
        """QK^T/AV score FLOPs per token, fwd+bwd (x3), both matmuls
        (x2). Per-query key count: seq/2 for the causal triangle, capped
        at the sliding window (Mistral/Mixtral) — mirrors GemmaConfig's
        local layers; without the cap, windowed runs at long seq_len
        report inflated model FLOPs and overstate MFU. Shared by the
        Llama and Mixtral flops_per_token (only their matmul term
        differs)."""
        keys = seq_len / 2
        if self.sliding_window is not None:
            keys = min(float(self.sliding_window), keys)
        return (
            6.0 * self.n_layers * self.n_heads * self.head_dim
            * 2.0 * keys
        )


# Presets. 8B matches Meta's Llama-3-8B shape; the proxies are the same
# architecture scaled to fit one v5e chip (16 GiB HBM) for bench/smoke runs.
#
# Backend policy: production-size presets (here and in the mixtral/
# gemma/deepseek families) train through attention_backend="flash" —
# the naive xla path materializes f32 [H, T, T] scores, which at
# seq 8192 / 32 heads is 8 GB PER TENSOR (measured compile-OOM, r5;
# docs/PERF.md block8b section) and cost 11 MFU points even where it
# fit. Tiny test presets stay on "xla": the suite runs them on CPU,
# where flash means the Pallas interpreter (slow), and the xla path is
# the reference the flash kernel is parity-tested against.
# decode_config() resets the backend for the KV-cache path.
LLAMA_CONFIGS: dict[str, LlamaConfig] = {
    "llama3_8b": LlamaConfig(attention_backend="flash"),
    # Llama-3.1-8B: same shape as 3.0, llama3 rope transform (Meta's
    # published scaling params are RopeScaling's defaults), 128k
    # context window. The flash kernel holds whole-sequence slabs in
    # VMEM and stops compiling between 8k and 16k positions per shard
    # (tpufw.ops.flash._check_slabs_fit): training at the full window
    # needs the sequence sharded (attention_backend "ring"/"ulysses").
    "llama31_8b": LlamaConfig(
        max_seq_len=131_072,
        rope_scaling=RopeScaling(),
        attention_backend="flash",
    ),
    "llama3_1b_proxy": LlamaConfig(
        vocab_size=32_768,
        d_model=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=8,
        head_dim=128,
        d_ff=8192,
        max_seq_len=4096,
        attention_backend="flash",
    ),
    "llama3_tiny": LlamaConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        max_seq_len=128,
        remat=False,
    ),
    # Mistral-7B (v0.1): Llama architecture + a 4096-token sliding
    # window on every layer.
    "mistral_7b": LlamaConfig(
        vocab_size=32_000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        d_ff=14_336,
        rope_theta=10_000.0,
        max_seq_len=32_768,
        sliding_window=4096,
        attention_backend="flash",
    ),
    "mistral_tiny": LlamaConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        max_seq_len=128,
        sliding_window=32,
        remat=False,
    ),
    # Qwen-2.5: the Llama architecture + qkv biases. 7B matches the HF
    # Qwen/Qwen2.5-7B shape (untied); the tiny is the test proxy.
    "qwen25_7b": LlamaConfig(
        vocab_size=152_064,
        d_model=3584,
        n_layers=28,
        n_heads=28,
        n_kv_heads=4,
        head_dim=128,
        d_ff=18_944,
        rope_theta=1_000_000.0,
        rms_eps=1e-6,
        max_seq_len=32_768,
        attention_qkv_bias=True,
        attention_backend="flash",
    ),
    "qwen25_tiny": LlamaConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        max_seq_len=128,
        remat=False,
        attention_qkv_bias=True,
    ),
}


def _scale_rope_freqs(
    freqs: jax.Array, s: RopeScaling
) -> jax.Array:
    """Frequency transforms matching HF's executed math so imported
    checkpoints are bit-comparable. "linear": every frequency divided
    by ``factor`` (position interpolation). "llama3"
    (``_compute_llama3_parameters``): components with wavelength beyond
    ``original_max/low_freq_factor`` are slowed by ``factor``, those
    below ``original_max/high_freq_factor`` are kept, and the band
    between is linearly interpolated in smooth-factor space."""
    if s.rope_type == "linear":
        return freqs / s.factor
    if s.rope_type != "llama3":
        raise NotImplementedError(
            f"rope_type={s.rope_type!r}: RopeScaling implements "
            "'llama3' and 'linear'"
        )
    old_len = float(s.original_max_position_embeddings)
    wavelen = 2.0 * math.pi / freqs
    scaled = jnp.where(
        wavelen > old_len / s.low_freq_factor, freqs / s.factor, freqs
    )
    smooth = (old_len / wavelen - s.low_freq_factor) / (
        s.high_freq_factor - s.low_freq_factor
    )
    smoothed = (1.0 - smooth) * freqs / s.factor + smooth * freqs
    is_medium = (wavelen <= old_len / s.low_freq_factor) & (
        wavelen >= old_len / s.high_freq_factor
    )
    return jnp.where(is_medium, smoothed, scaled)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    scaling: Optional[Any] = None,
    rotary_dim: Optional[int] = None,
) -> jax.Array:
    """Rotary embeddings. x: [B, T, H, D], positions: [B, T] -> same shape.

    ``rotary_dim`` < D rotates the first ``rotary_dim`` dimensions of
    each head (split-half among themselves, HF's partial rotary) and
    passes the rest through. ``scaling`` is a ``RopeScaling`` or a
    ``tpufw.models.deepseek.YarnScaling``, whose attention factor
    multiplies cos and sin: the rotated part alone."""
    d = x.shape[-1] if rotary_dim is None else int(rotary_dim)
    if d != x.shape[-1]:
        rotated = apply_rope(x[..., :d], positions, theta, scaling)
        return jnp.concatenate([rotated, x[..., d:]], axis=-1)
    freqs = 1.0 / (
        theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    )  # [D/2]
    att = 1.0
    if isinstance(scaling, RopeScaling):
        freqs = _scale_rope_freqs(freqs, scaling)
    elif scaling is not None:
        # YarnScaling lives with the family that brought it, which
        # imports this module.
        freqs = scaling.inv_freqs(d, theta)
        att = scaling.resolved_attention_factor()
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if att != 1.0:
        out = out * att
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    # Gemma parameterization: weight stored as an offset from 1 (zeros
    # init, applied as 1 + w) — matches HF so checkpoints interchange.
    offset: bool = False

    @nn.compact
    def __call__(self, x):
        init = (
            nn.initializers.zeros_init()
            if self.offset
            else nn.initializers.ones_init()
        )
        w = self.param(
            "scale",
            nn.with_logical_partitioning(init, ("norm",)),
            (x.shape[-1],),
            jnp.float32,
        )
        return rms_norm(x, w + 1.0 if self.offset else w, self.eps)


def lora_delta(cfg, x, features, axis, in_names, out_names, name):
    """Low-rank adapter delta for the projection ``name``: x @ A @ B
    scaled by alpha/rank; 0.0 when LoRA is off. A uses the projection's
    fan-in init, B starts at ZERO — step 0 output equals the base model,
    the standard LoRA init. Params land as ``{name}_lora_a/b`` siblings
    of the base module, so a base-only checkpoint stays a strict subtree
    (import/export and bare-params restore are unaffected)."""
    r = getattr(cfg, "lora_rank", 0)
    if not r:
        return 0.0
    a = nn.DenseGeneral(
        features=r,
        axis=axis,
        use_bias=False,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), (*in_names, "lora")
        ),
        name=f"{name}_lora_a",
    )(x)
    b = nn.DenseGeneral(
        features=features,
        axis=-1,
        use_bias=False,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), ("lora", *out_names)
        ),
        name=f"{name}_lora_b",
    )(a)
    return b * (getattr(cfg, "lora_alpha", 16.0) / r)


def reject_quant_lora(cfg) -> None:
    """The one statement of the serving invariant: int8 weights carry no
    gradient path, so adapters must be merged (tools/merge_lora) before
    quantizing. Shared by every quantized module (llama.projection,
    mixtral MoEMLP)."""
    if getattr(cfg, "lora_rank", 0):
        raise ValueError(
            "quantized_weights with lora_rank > 0: merge the "
            "adapters (tools/merge_lora) before quantizing"
        )


class QuantDenseGeneral(nn.Module):
    """DenseGeneral over int8 weights + per-output-channel scales —
    the serving twin of the fp projection (tpufw.ops.quant). Param
    shapes match ``quantize_params`` output; logical axes mirror the fp
    kernel's so sharded serving lays out identically."""

    features: Any
    axis: Any
    dtype: Any
    in_names: tuple
    out_names: tuple
    use_bias: bool = False

    @nn.compact
    def __call__(self, x):
        from tpufw.ops.quant import quant_contract

        axes = (
            (self.axis,) if isinstance(self.axis, int) else tuple(self.axis)
        )
        n_in = len(axes)
        in_dims = tuple(x.shape[a] for a in axes)
        out_dims = (
            (self.features,)
            if isinstance(self.features, int)
            else tuple(self.features)
        )
        q = self.param(
            "q_kernel",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(),
                (*self.in_names, *self.out_names),
            ),
            (*in_dims, *out_dims),
            jnp.int8,
        )
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(
                nn.initializers.ones_init(), self.out_names
            ),
            out_dims,
            jnp.float32,
        )
        y = quant_contract(x.astype(self.dtype), q, scale, n_in)
        if self.use_bias:
            b = self.param(
                "bias",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), self.out_names
                ),
                out_dims,
                jnp.float32,
            )
            y = y + b.astype(y.dtype)
        return y


def projection(
    cfg, x, features, axis, in_names, out_names, name, use_bias=False
):
    """Dense projection + optional LoRA delta — the ONE composition every
    adapted matmul (attention q/k/v/o, MLP gate/up/down) goes through.
    Must be called from inside a compact ``__call__``. With
    ``cfg.quantized_weights`` the int8 serving twin is declared instead
    (mutually exclusive with LoRA — merge adapters first); biased
    projections (Qwen qkv) keep a full-precision bias vector either way
    (it is tiny — the kernel carries the bandwidth)."""
    if getattr(cfg, "quantized_weights", False):
        reject_quant_lora(cfg)
        return QuantDenseGeneral(
            features=features,
            axis=axis,
            dtype=cfg.dtype,
            in_names=tuple(in_names),
            out_names=tuple(out_names),
            use_bias=use_bias,
            name=name,
        )(x)
    base = nn.DenseGeneral(
        features=features,
        axis=axis,
        use_bias=use_bias,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), (*in_names, *out_names)
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), tuple(out_names)
        ),
        name=name,
    )(x)
    return base + lora_delta(
        cfg, x, features, axis, in_names, out_names, name
    )


class Attention(nn.Module):
    cfg: LlamaConfig
    # Sliding-window size for this layer (None = global attention).
    # Gemma-2 alternates local/global layers, so this is per-block.
    window: Optional[int] = None
    # Query heads of THIS layer over the model's ``n_kv_heads`` (None =
    # cfg.n_heads; tpufw.models.laguna: 48 on global, 72 on window layers).
    n_heads: Optional[int] = None
    # This layer's rotary embedding (None = the model's one:
    # cfg.rope_theta / cfg.rope_scaling over the whole head).
    rope: Optional[LayerRope] = None

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        n_heads = cfg.n_heads if self.n_heads is None else self.n_heads
        qkv_bias = getattr(cfg, "attention_qkv_bias", False)
        q = projection(
            cfg, x, (n_heads, cfg.head_dim), -1,
            ("embed",), ("q_heads", "head_dim"), "q", use_bias=qkv_bias,
        )
        k = projection(
            cfg, x, (cfg.n_kv_heads, cfg.head_dim), -1,
            ("embed",), ("kv_heads", "head_dim"), "k", use_bias=qkv_bias,
        )
        v = projection(
            cfg, x, (cfg.n_kv_heads, cfg.head_dim), -1,
            ("embed",), ("kv_heads", "head_dim"), "v", use_bias=qkv_bias,
        )
        if getattr(cfg, "qk_norm", None):
            # QK-norm over the WHOLE projected width, before the split
            # into heads means anything (OLMo 2's placement; tpufw.models.
            # olmo_hybrid): one learned scale of H * hd for q, one of
            # Hk * hd for k.
            q, k = (
                RMSNorm(cfg.rms_eps, name=name)(
                    a.reshape(*a.shape[:-2], -1)
                ).reshape(a.shape)
                for name, a in (("q_norm", q), ("k_norm", k))
            )
        if self.rope is not None:
            rope = self.rope
            q = apply_rope(
                q, positions, rope.theta, rope.scaling, rope.rotary_dim
            )
            k = apply_rope(
                k, positions, rope.theta, rope.scaling, rope.rotary_dim
            )
        elif getattr(cfg, "use_rope", True):
            rope_scaling = getattr(cfg, "rope_scaling", None)
            q = apply_rope(q, positions, cfg.rope_theta, rope_scaling)
            k = apply_rope(k, positions, cfg.rope_theta, rope_scaling)
        # else NoPE: no position signal; causality alone orders tokens.
        key_multiplier = getattr(cfg, "key_multiplier", None)
        if key_multiplier is not None:
            # A muP multiplier on the keys (tpufw.models.falcon_h1), the
            # config's own number applied at run time.
            k = k * jnp.asarray(key_multiplier, k.dtype)
        # Non-default query scaling (Gemma's query_pre_attn_scalar):
        # backends scale by head_dim**-0.5 internally, so pre-multiply q
        # by the ratio to the desired qpas**-0.5.
        qpas = getattr(cfg, "query_pre_attn_scalar", None)
        if qpas is not None and float(qpas) != float(cfg.head_dim):
            q = q * (math.sqrt(cfg.head_dim) / math.sqrt(float(qpas)))
        q = nn.with_logical_constraint(
            q, ("batch", "act_seq", "act_heads", "head_dim")
        )
        k = nn.with_logical_constraint(
            k, ("batch", "act_seq", "act_heads", "head_dim")
        )
        v = nn.with_logical_constraint(
            v, ("batch", "act_seq", "act_heads", "head_dim")
        )
        causal = getattr(cfg, "causal", True)
        if not causal and self.window is not None:
            # The window mask is causal-relative (last-N PAST keys);
            # under causal=False it would pass every FUTURE key while
            # capping the past — an incoherent asymmetric mask, not
            # bidirectional attention. LLM2Vec-on-Mistral must disable
            # the window (sliding_window=None) explicitly.
            raise ValueError(
                "causal=False with sliding_window set: the window mask "
                "is causal-relative; set sliding_window=None for "
                "bidirectional embedding fine-tuning"
            )
        if cfg.decode:
            if not causal:
                raise ValueError(
                    "causal=False with decode=True: a KV cache is a "
                    "causal construct — bidirectional models embed, "
                    "they don't autoregress"
                )
            out = self._cached_attention(q, k, v, segment_ids)
        else:
            out = multi_head_attention(
                q,
                k,
                v,
                causal=causal,
                segment_ids=segment_ids,
                logits_soft_cap=getattr(cfg, "attn_logit_soft_cap", None),
                sliding_window=self.window,
                backend=cfg.attention_backend,
            )
        gated = getattr(cfg, "attn_output_gate", False)
        if gated == "per_head":
            # One sigmoid scalar a head, from the layer's input ([d, H]).
            gate = projection(
                cfg, x, n_heads, -1, ("embed",), ("q_heads",), "gate"
            )
            out = out * nn.sigmoid(gate)[..., None]
        elif gated:
            # Elementwise sigmoid gate on the heads' output, taken from
            # the layer's input (a flat [d, H*hd] kernel, so the int8
            # path's table reads it like an MLP's ``gate``).
            gate = projection(
                cfg, x, n_heads * cfg.head_dim, -1,
                ("embed",), ("heads",), "gate",
            )
            out = out * nn.sigmoid(gate).reshape(out.shape)
        return projection(
            cfg, out, cfg.d_model, (-2, -1),
            ("heads", "head_dim"), ("embed",), "o",
        )

    def _cached_attention(self, q, k, v, segment_ids):
        """KV-cache step: append this call's k/v to the store, then attend
        q (at the slots it was written to) over the live prefix of the
        logical row, the rung of the store's ladder that holds every live
        row, for the pool's live rows (tpufw.ops.kv_store: layouts, both
        bounds, masking and clamp rationale)."""
        return cached_attention(
            self, self.cfg, q, k, v, segment_ids, self.window,
            getattr(self.cfg, "attn_logit_soft_cap", None),
        )[0]


def cached_attention(
    module, cfg, q, k, v, segment_ids, window=None, soft_cap=None
):
    """``Attention``'s cached step, from inside flax ``module`` (which
    declares the cache leaves). Returns ``(out, reader)``: ``reader(q2)``
    attends other queries, at the slots this call's sat at, over the same
    cache, for a later layer that stores nothing of its own
    (tpufw.ops.kv_store, READERS THAT ARE NOT THE WRITER); None for a
    ring, which only its own layer reads."""
    if window is not None and getattr(cfg, "window_ring", False):
        # A family whose window is a small part of its context in
        # most layers (its config's constant ``window_ring``) keeps
        # a RING of the last ``window`` keys per row in place of a
        # row of max_seq_len it masks. The pools then share no
        # prefix page, export no slot and verify no block (kv_store
        # ``Role.per_slot``), which is why the one-window-everywhere
        # presets (Mistral: 4096 of 32k) keep the mask: they would
        # lose those for a cache 8x smaller. The ring is not in slot
        # order: the mask reads each key's own logical slot
        # (``kv_slots``).
        read, seg, q_slots = kv_store.ring_append(
            module, cfg, {"ring_key": k, "ring_value": v}, segment_ids,
            window,
        )
        return read(
            lambda views, kv_seg, kv_slots: multi_head_attention(
                q,
                views["ring_key"],
                views["ring_value"],
                causal=True,
                segment_ids=seg,
                kv_segment_ids=kv_seg,
                q_positions=q_slots,
                kv_positions=kv_slots,
                logits_soft_cap=soft_cap,
                sliding_window=window,
                backend="xla",
            )
        ), None
    read, seg, q_slots = kv_store.append(
        module, cfg, {"cached_key": k, "cached_value": v}, segment_ids
    )
    attend = _AttendHeads(soft_cap, window)

    def reader(q2):
        return read(attend, (q2, seg, q_slots))

    return reader(q), reader


@dataclasses.dataclass(frozen=True)
class _AttendHeads:
    """``kv_store.append``'s ``attend`` for K/V heads: the K rows' queries
    over the L slots of the same rows the store shows. Hashable by value:
    the layers of a model share one trace of each branch."""

    soft_cap: Optional[float]
    window: Optional[int]

    def __call__(self, views, kv_seg, rows):
        q, seg, q_slots = rows
        return multi_head_attention(
            q,
            views["cached_key"],
            views["cached_value"],
            causal=True,
            segment_ids=seg,
            kv_segment_ids=kv_seg,
            q_positions=q_slots,
            logits_soft_cap=self.soft_cap,
            sliding_window=self.window,
            backend="xla",
        )

    @property
    def paged(self):
        """The same contraction over the rows' pages IN PLACE, for a
        call of one token a row (``kv_store.append``'s ``read`` asks):
        None with a window, whose mask the kernel does not carry."""
        return None if self.window is not None else self._paged

    def _paged(self, arenas, kv_seg, table, lens, rows, *, heads=None):
        """``arenas`` the K and V page arenas ``[n_pages, page, K, hd]``
        as stored (the first ``heads`` of K the model's: None, all),
        ``kv_seg`` [B, S] the logical slots' segment ids, ``table`` the
        page table, ``lens`` [B] the slots each row attends (0: not
        live), ``rows`` the ``per_row`` of ``__call__`` at t == 1."""
        q, seg, _ = rows
        out = paged_attend.paged_attention(
            q[:, 0],
            arenas["cached_key"],
            arenas["cached_value"],
            table,
            lens,
            kv_seg == seg,
            kv_heads=heads,
            logits_soft_cap=self.soft_cap,
        )
        return out[:, None]


class MLP(nn.Module):
    """SwiGLU feed-forward. ``d_ff`` overrides the config width
    (DeepSeek shared experts size theirs as a multiple of the expert
    width, not cfg.d_ff)."""

    cfg: LlamaConfig
    d_ff: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d_ff = self.d_ff if self.d_ff is not None else cfg.d_ff
        gate = projection(
            cfg, x, d_ff, -1, ("embed",), ("mlp",), "gate"
        )
        up = projection(cfg, x, d_ff, -1, ("embed",), ("mlp",), "up")
        # muP multipliers on the gate's pre-activation and on the output
        # (tpufw.models.falcon_h1), None elsewhere.
        gate_mult, down_mult = (
            getattr(cfg, "mlp_multipliers", None) or (None, None)
        )
        if gate_mult is not None:
            gate = gate * jnp.asarray(gate_mult, gate.dtype)
        act_name = getattr(cfg, "mlp_activation", "silu")
        if act_name == "silu":
            act = nn.silu(gate)
        elif act_name == "gelu_tanh":  # Gemma GeGLU
            act = nn.gelu(gate, approximate=True)
        else:
            raise ValueError(f"unknown mlp_activation {act_name!r}")
        h = act * up
        h = nn.with_logical_constraint(h, ("batch", "act_seq", "act_mlp"))
        out = projection(
            cfg, h, cfg.d_model, -1, ("mlp",), ("embed",), "down"
        )
        if down_mult is not None:
            out = out * jnp.asarray(down_mult, out.dtype)
        return out


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

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
        x = x + MLP(cfg, name="mlp")(RMSNorm(cfg.rms_eps, name="mlp_norm")(x))
        return nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))


def unstack_layer_params(params: dict, donate: bool = False) -> dict:
    """Scanned-trunk param tree -> the unscanned twin's tree.

    ``decoder_lm`` with ``scan_layers=True`` stores the block stack as
    ONE submodule named "layers" whose leaves carry a leading [L] axis
    (nn.scan variable_axes); with ``scan_layers=False`` the same
    weights live under ``layer_0 .. layer_{L-1}``. This converts the
    former to the latter — the serving "unroll" lever: a checkpoint
    trained scanned can be decoded by the unscanned twin
    (``dataclasses.replace(cfg, scan_layers=False)``), which skips the
    per-step per-layer weight slicing of the decode scan. Works for
    every decoder_lm family (Llama/Qwen/Mistral/Mixtral/Deepseek and
    Gemma, whose scanned unit is a PAIR), and for a trunk of several
    stacks, each under a key that ends in "_layers". A tree with no such
    key (already unscanned) is returned unchanged.

    With ``donate=True`` each stacked leaf is explicitly DELETED once
    its per-layer slices exist, so peak device memory is the weights
    plus one stacked leaf — not 2x the weights, which would OOM
    serving startup for any model past half of HBM. (Explicit delete,
    not jit donation: the stacked buffer can never alias the smaller
    tuple-of-slices outputs, so donation would just warn and free —
    this frees without the warning, on every backend.) Consequence:
    the input tree's "layers" leaves are INVALID afterwards — only
    enable when the caller drops the old tree immediately (the serve
    paths do); the default keeps the input usable."""
    stacks = [k for k in params if k == "layers" or k.endswith("_layers")]
    if not stacks:
        return params
    out = {k: v for k, v in params.items() if k not in stacks}
    for stack in stacks:
        # "layers" -> "layer_{i}"; a trunk of several stacks
        # (tpufw.models.phi4flash: "self_layers", "cross_layers") names
        # each the same way: "self_layer_{i}".
        leaves, treedef = jax.tree_util.tree_flatten(params[stack])
        n = leaves[0].shape[0]
        split = jax.jit(lambda a, n=n: tuple(a[i] for i in range(n)))
        per_leaf = []
        for leaf in leaves:
            cut = split(leaf)
            if donate and isinstance(leaf, jax.Array):
                # The slices must exist on device before the source dies.
                jax.block_until_ready(cut)
                leaf.delete()
            per_leaf.append(cut)
        for i in range(n):
            out[f"{stack[:-1]}_{i}"] = jax.tree_util.tree_unflatten(
                treedef, [pl[i] for pl in per_leaf]
            )
    return out


def decoder_lm(
    cfg, block_base, tokens, positions, segment_ids, with_aux,
    return_hidden=False,
):
    """Shared decoder trunk: embed -> remat/scan block stack -> norm -> head.

    Used by both Llama and Mixtral (the only difference is the block class
    and whether blocks thread an aux-loss carry) so the two families can't
    drift. Must be called from inside a compact ``__call__``.

    Returns ``logits`` or ``(logits, aux)`` when ``with_aux``. With
    ``return_hidden`` the head matmul is skipped and the post-final-norm
    hidden states [B, T, D] take the place of logits — the chunked-vocab
    loss path (tpufw.ops.loss) computes CE straight from these plus the
    head kernel, never materializing [B, T, V].
    """
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    # Scaled-embedding models (Gemma) store embeddings ~1/sqrt(d) and
    # multiply by sqrt(d) at lookup, keeping the TIED head's logits O(1);
    # initializing at stddev 1.0 there would saturate the final soft-cap
    # from step 0 (observed: init loss 29 vs ln(V)~5.5).
    embed_std = (
        cfg.d_model ** -0.5 if getattr(cfg, "embed_scale", False) else 1.0
    )
    embed = nn.Embed(
        cfg.vocab_size,
        cfg.d_model,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        embedding_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=embed_std), ("vocab", "embed")
        ),
        name="embed",
    )
    x = embed(tokens)
    if getattr(cfg, "embed_scale", False):
        # Gemma scales embeddings by sqrt(d_model), cast through the
        # activation dtype exactly as HF does (bf16 rounding included).
        x = x * jnp.asarray(
            math.sqrt(cfg.d_model), cfg.dtype
        ).astype(x.dtype)
    embedding_multiplier = getattr(cfg, "embedding_multiplier", None)
    if embedding_multiplier is not None:
        x = x * jnp.asarray(embedding_multiplier, x.dtype)
    x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))

    block_cls = block_base
    if cfg.remat:
        policy_name = getattr(cfg, "remat_policy", "dots")
        if policy_name not in _REMAT_POLICIES:
            raise ValueError(
                f"unknown remat_policy {policy_name!r}; choose from "
                f"{sorted(_REMAT_POLICIES)}"
            )
        block_cls = nn.remat(
            block_base,
            policy=_REMAT_POLICIES[policy_name],
            prevent_cse=not cfg.scan_layers,
        )
    aux = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:

        def body(mdl, carry, _):
            h, aux_acc = carry
            out = mdl(h, positions, segment_ids)
            if with_aux:
                h, a = out
                return (h, aux_acc + a), None
            return (out, aux_acc), None

        (x, aux), _ = nn.scan(
            body,
            variable_axes={"params": 0, "cache": 0},
            split_rngs={"params": True},
            length=cfg.n_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(block_cls(cfg, name="layers"), (x, aux), None)
    else:
        for i in range(cfg.n_layers):
            out = block_cls(cfg, name=f"layer_{i}")(x, positions, segment_ids)
            if with_aux:
                x, a = out
                aux = aux + a
            else:
                x = out

    x = RMSNorm(
        cfg.rms_eps,
        offset=getattr(cfg, "rms_offset", False),
        name="final_norm",
    )(x)
    if return_hidden:
        return (x, aux) if with_aux else x
    if cfg.tie_embeddings:
        logits = embed.attend(x.astype(jnp.float32))
    elif getattr(cfg, "quantized_weights", False):
        logits = QuantDenseGeneral(
            features=cfg.vocab_size,
            axis=-1,
            dtype=jnp.float32,
            in_names=("embed",),
            out_names=("vocab",),
            name="lm_head",
        )(x)
    else:
        logits = nn.DenseGeneral(
            features=cfg.vocab_size,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            name="lm_head",
        )(x)
    lm_head_multiplier = getattr(cfg, "lm_head_multiplier", None)
    if lm_head_multiplier is not None:
        logits = logits * jnp.asarray(lm_head_multiplier, logits.dtype)
    logits = nn.with_logical_constraint(
        logits, ("batch", "act_seq", "act_vocab")
    )
    return (logits, aux) if with_aux else logits


class Llama(nn.Module):
    """Decoder-only Llama-3 LM. Returns logits [B, T, vocab]."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self, tokens, positions=None, segment_ids=None, return_hidden=False
    ):
        return decoder_lm(
            self.cfg, LlamaBlock, tokens, positions, segment_ids, False,
            return_hidden=return_hidden,
        )
