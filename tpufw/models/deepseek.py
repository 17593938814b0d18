"""DeepSeek-V2 family: Multi-head Latent Attention (MLA), TPU-first.

The reference has no ML layer at all (its workload is ``nvidia-smi``,
reference ``README.md:314``); this family joins Llama/Mistral/Qwen/
Mixtral/Gemma-2 because MLA is THE architecture whose win is
memory-system-shaped — exactly what a TPU framework should exploit:

- **Latent KV cache.** Attention keys/values are low-rank: one shared
  latent ``c_kv = x @ W_dkv`` of ``kv_lora_rank`` dims (plus a small
  decoupled-RoPE key) is cached instead of per-head K and V. For the
  V2-Lite shape the cache is ``(512 + 64)`` floats/token vs Llama-8B's
  ``2 * 8 * 128 = 2048`` — 3.6x less HBM, and decode is HBM-bound.
- **Absorbed decode.** The decode path never expands the latents back
  to per-head K/V: ``W_uk`` is absorbed into the query (scores are
  taken IN latent space against the cached ``c_kv``) and ``W_uv`` is
  applied once to the attention-weighted latents — per step the cache
  traffic is the latent, not H-times-expanded tensors. Training uses
  the expanded form (one big MXU-friendly einsum per projection);
  tests/test_deepseek.py pins prefill-vs-decode equivalence between
  the two forms.
- **Decoupled RoPE.** Rotary position goes through a separate
  ``qk_rope_head_dim`` slice (queries per head, ONE shared key slice),
  because a position rotation applied to the latent would break its
  low-rank factorization. DeepSeek rotates INTERLEAVED pairs (HF
  ``view_as_complex`` layout), unlike Llama's split-half — matched
  here exactly for checkpoint parity.

Structure mirrors tpufw.models.llama (same decoder trunk, RMSNorm,
SwiGLU MLP, remat policies, logical sharding axes) so every trainer,
parallelism mode, and tool that consumes the trunk applies unchanged.
The MoE FFN (DeepSeek's fine-grained routed experts + always-on shared
experts) rides the Mixtral einsum dispatch (tpufw.models.mixtral
MoEMLP) with the V2 gate conventions: raw softmax top-k mass (no
renormalization — matching the HF reference's executed behavior) times
``routed_scaling_factor``, plus group-limited selection (the 236B/Chat
``topk_method="group_limited_greedy"`` — ``n_group``/``topk_group``)
and yarn long-context rope scaling. ``moe_scoring="sigmoid"`` scores
each expert alone and chooses by score + a selection bias (the V3
convention, tpufw.ops.moe), and ``experts_held`` tells the layer which
routed experts this chip holds; tpufw.models.solar_open2 runs both.
Remaining import rejections (tools/import_hf.py): other topk_methods
(e.g. V3's noaux_tc group-limited form), a checkpoint's selection bias,
sparse ``moe_layer_freq``, and attention bias.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from tpufw.models.llama import (
    MLP,
    Dtype,
    RMSNorm,
    decoder_lm,
    projection,
)
from tpufw.models.mixtral import MoEMLP
from tpufw.ops import kv_store
from tpufw.ops.attention import attention_mask, multi_head_attention


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    """DeepSeek-V2 MLA decoder. Field names follow the HF config where
    the concepts coincide (cited: huggingface
    ``DeepseekV2Config`` / ``modeling_deepseek_v2.py``)."""

    vocab_size: int = 32_768
    d_model: int = 2048
    n_layers: int = 12
    n_heads: int = 16
    # None = full-rank q projection (the V2-Lite choice); an int adds
    # the compressed q path (q_a -> norm -> q_b, the V2 236B choice).
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 8192
    rope_theta: float = 10_000.0
    # Yarn long-context scaling (V2/V2-Lite checkpoints); None = plain.
    rope_scaling: Optional["YarnScaling"] = None
    max_seq_len: int = 4096
    rms_eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    # "xla" (einsum, the correctness reference), "flash" (Pallas
    # kernel), "ring" (sequence-parallel neighbor exchange), or
    # "ulysses" (head/sequence all-to-all) over the `sequence` mesh
    # axis: MLA's v head dim is smaller than qk's, so the non-xla
    # backends zero-pad v up to qk_head_dim and slice the output back
    # — exact (padded value columns contribute zeros) at ~dv/qk_dim
    # extra v memory.
    attention_backend: str = "xla"
    # MoE dispatch implementation — see MixtralConfig.moe_dispatch
    # ("einsum" shards over the expert axis; "sorted" runs grouped
    # ragged_dot matmuls for single-device/data-sharded training).
    moe_dispatch: str = "einsum"
    remat: bool = True
    remat_policy: str = "dots"
    scan_layers: bool = True
    decode: bool = False
    tie_embeddings: bool = False
    # int8 weight-only serving (tpufw.ops.quant): projections and
    # routed/shared experts go int8; kv_b and routers stay fp.
    quantized_weights: bool = False
    # Paged latent-KV cache — same contract as tpufw.models.llama
    # LlamaConfig.kv_page/kv_pages/kv_quant, applied to the c_kv/k_pe
    # latent arenas (tpufw.infer.pages).
    kv_page: int = 0
    kv_pages: int = 0
    kv_quant: str = ""
    # --- DeepSeek MoE FFN (0 routed experts = dense everywhere) ---
    # Fine-grained routed experts per MoE layer.
    n_routed_experts: int = 0
    experts_per_token: int = 6
    # Width of EACH routed/shared expert (HF moe_intermediate_size) —
    # much narrower than the dense d_ff.
    moe_d_ff: int = 1408
    # Always-on shared experts (one fused MLP of n_shared * moe_d_ff).
    n_shared_experts: int = 2
    # Layers [0, first_k_dense) keep the dense MLP (HF
    # first_k_dense_replace). > 0 requires scan_layers=False — a scan
    # needs homogeneous layers.
    first_k_dense: int = 0
    # Multiplier on the routed output (HF routed_scaling_factor).
    routed_scaling_factor: float = 1.0
    # Renormalize top-k gate mass (False = V2 convention: raw softmax).
    norm_topk_prob: bool = False
    # Group-limited selection (HF topk_method="group_limited_greedy",
    # the 236B/Chat routing): experts partition into n_group groups,
    # only the topk_group best groups (by max score) are routable.
    # n_group=0 disables (plain greedy, the V2-Lite choice).
    n_group: int = 0
    topk_group: int = 0
    # How the router scores ("softmax": V2; "sigmoid" with a selection
    # bias: the V3 convention, tpufw.ops.moe._topk_select), and which
    # routed experts this chip holds of each layer, (first, n); None =
    # all (tpufw.models.mixtral.MoEMLP.held).
    moe_scoring: str = "softmax"
    experts_held: Optional[tuple] = None
    # GShard capacity discipline for the einsum dispatch; imports
    # default to dropless (n_routed_experts) like Mixtral's.
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.02
    router_z_weight: float = 1e-3

    @property
    def n_experts(self) -> int:
        """Alias: tpufw.models.mixtral.MoEMLP reads ``cfg.n_experts``."""
        return self.n_routed_experts

    @property
    def moe(self) -> bool:
        return self.n_routed_experts > 0

    def __post_init__(self):
        if self.moe and self.first_k_dense > 0 and self.scan_layers:
            raise ValueError(
                "first_k_dense > 0 mixes dense and MoE layers — "
                "nn.scan needs homogeneous layers; set "
                "scan_layers=False (imports do this automatically)"
            )

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def decode_config(self) -> "DeepseekConfig":
        """Inference twin: latent KV cache on, remat off. The backend
        resets to "xla" to honor the family-wide decode contract
        (llama/gemma do the same): the absorbed-latent decode path
        hand-rolls its attention and never reads the field today, but
        a flash-defaulted train preset must not leak "flash" into a
        decode config that future code may consult."""
        return dataclasses.replace(
            self, decode=True, remat=False, attention_backend="xla"
        )

    def n_params(self, include_embed: bool = True) -> int:
        d, l, h = self.d_model, self.n_layers, self.n_heads
        if self.q_lora_rank is None:
            q = d * h * self.qk_head_dim
            q_norms = 0
        else:
            q = self.q_lora_rank * (d + h * self.qk_head_dim)
            q_norms = self.q_lora_rank
        kv_a = d * (self.kv_lora_rank + self.qk_rope_head_dim)
        kv_b = self.kv_lora_rank * h * (
            self.qk_nope_head_dim + self.v_head_dim
        )
        o = h * self.v_head_dim * d
        attn = l * (q + kv_a + kv_b + o)
        n_moe_layers = (
            max(0, l - self.first_k_dense) if self.moe else 0
        )
        n_dense_layers = l - n_moe_layers
        mlp = n_dense_layers * 3 * d * self.d_ff
        if n_moe_layers:
            per_layer = (
                3 * d * self.moe_d_ff * self.n_routed_experts  # routed
                + d * self.n_routed_experts  # router
                + 3 * d * self.moe_d_ff * self.n_shared_experts  # shared
            )
            mlp += n_moe_layers * per_layer
        norms = (2 * l + 1) * d + l * (self.kv_lora_rank + q_norms)
        total = attn + mlp + norms
        if include_embed:
            head = 0 if self.tie_embeddings else self.vocab_size * d
            total += self.vocab_size * d + head
        return total

    def flops_per_token(self, seq_len: int) -> float:
        """Training FLOPs/token: 6*N_active_matmul + attention score
        FLOPs (causal-halved, x3 fwd+bwd, both QK^T and AV matmuls) —
        same convention as Llama/MixtralConfig.flops_per_token. Under
        MoE only experts_per_token routed experts run per token."""
        n_matmul = (
            self.n_params(include_embed=False)
            # norms aren't matmuls; head is.
            - (2 * self.n_layers + 1) * self.d_model
            - self.n_layers * (
                self.kv_lora_rank
                + (self.q_lora_rank or 0)
            )
            + self.d_model * self.vocab_size
        )
        if self.moe:
            # Swap total routed weights for the ACTIVE k experts.
            n_moe_layers = max(0, self.n_layers - self.first_k_dense)
            routed = 3 * self.d_model * self.moe_d_ff
            n_matmul -= n_moe_layers * routed * (
                self.n_routed_experts - self.experts_per_token
            )
        keys = seq_len / 2
        score = (
            6.0 * self.n_layers * self.n_heads
            * (self.qk_head_dim + self.v_head_dim) * keys
        )
        return 6.0 * n_matmul + score


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """Yarn long-context rope scaling (arXiv 2309.00071), matching the
    transformers reference EXACTLY (modeling_rope_utils.py
    _compute_yarn_parameters): per-dimension ramp between interpolated
    (freq / factor) and extrapolated (unscaled) frequencies, plus an
    ``attention_factor`` multiplied into cos/sin. Note the reference's
    executed behavior: when ``mscale == mscale_all_dim`` (DeepSeek-
    V2-Lite publishes 0.707 for both) the factor is exactly 1.0, and
    transformers applies NO mscale^2 to the softmax scale — parity
    targets what the reference runs, not the original repo's
    remote-code variant."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    # 0.0 = unset (falsy): the ratio branch of the attention factor
    # needs BOTH mscale fields, exactly like the transformers gate.
    mscale: float = 0.0
    mscale_all_dim: float = 0.0
    attention_factor: Optional[float] = None  # None = derive below
    truncate: bool = True

    def inv_freqs(self, d: int, theta: float) -> jax.Array:
        """[d/2] inverse frequencies over ``d`` rotated dimensions (what
        tpufw.models.llama.apply_rope asks a scaling it does not own)."""
        return _yarn_freqs(d, theta, self)

    def resolved_attention_factor(self) -> float:
        import math

        def get_mscale(scale, m=1.0):
            if scale <= 1:
                return 1.0
            return 0.1 * m * math.log(scale) + 1.0

        if self.attention_factor is not None:
            return float(self.attention_factor)
        if self.mscale and self.mscale_all_dim:
            return get_mscale(self.factor, self.mscale) / get_mscale(
                self.factor, self.mscale_all_dim
            )
        return get_mscale(self.factor)


def _yarn_freqs(d: int, theta: float, s: YarnScaling) -> jax.Array:
    """[d/2] yarn inverse frequencies (transformers
    _compute_yarn_parameters, truncate semantics included)."""
    import math

    pos_freqs = theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    inv_extra = 1.0 / pos_freqs
    inv_inter = 1.0 / (s.factor * pos_freqs)

    def correction_dim(n_rot: float) -> float:
        return (
            d
            * math.log(
                s.original_max_position_embeddings / (n_rot * 2 * math.pi)
            )
        ) / (2 * math.log(theta))

    low = correction_dim(s.beta_fast)
    high = correction_dim(s.beta_slow)
    if s.truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low),
        0.0,
        1.0,
    )
    extrapolation_factor = 1.0 - ramp
    return (
        inv_inter * (1.0 - extrapolation_factor)
        + inv_extra * extrapolation_factor
    )


def apply_rope_interleaved(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    scaling: Optional[YarnScaling] = None,
) -> jax.Array:
    """DeepSeek rotary: INTERLEAVED pairs (x[2i], x[2i+1]) form the
    complex components (HF ``view_as_complex`` layout,
    modeling_deepseek_v2.py apply_rotary_emb) — NOT Llama's split-half.
    x: [B, T, H, D], positions: [B, T]. With yarn ``scaling``, the
    frequencies follow the ramp and the rotated output is multiplied by
    the attention factor (the reference multiplies cos/sin; rotation is
    linear, so scaling the output is identical)."""
    d = x.shape[-1]
    if scaling is None:
        freqs = 1.0 / (
            theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        )
        att = 1.0
    else:
        freqs = _yarn_freqs(d, theta, scaling)
        att = scaling.resolved_attention_factor()
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).reshape(x.shape)
    if att != 1.0:
        out = out * att
    return out.astype(x.dtype)


class MLAttention(nn.Module):
    """Multi-head Latent Attention: expanded form for training,
    absorbed latent form for KV-cache decode."""

    cfg: DeepseekConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        h, dn, dr, dv = (
            cfg.n_heads,
            cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim,
            cfg.v_head_dim,
        )

        # Queries: full-rank, or compressed (q_a -> norm -> q_b).
        if cfg.q_lora_rank is None:
            q = projection(
                cfg, x, (h, cfg.qk_head_dim), -1,
                ("embed",), ("q_heads", "head_dim"), "q",
            )
        else:
            cq = projection(
                cfg, x, cfg.q_lora_rank, -1,
                ("embed",), ("q_latent",), "q_a",
            )
            cq = RMSNorm(cfg.rms_eps, name="q_a_norm")(cq)
            q = projection(
                cfg, cq, (h, cfg.qk_head_dim), -1,
                ("q_latent",), ("q_heads", "head_dim"), "q_b",
            )
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        q_pe = apply_rope_interleaved(
            q_pe, positions, cfg.rope_theta, cfg.rope_scaling
        )

        # Shared KV latent + decoupled-rope key (one "head").
        ckv_kr = projection(
            cfg, x, cfg.kv_lora_rank + dr, -1,
            ("embed",), ("kv_latent",), "kv_a",
        )
        c_kv = RMSNorm(cfg.rms_eps, name="kv_a_norm")(
            ckv_kr[..., : cfg.kv_lora_rank]
        )
        k_pe = apply_rope_interleaved(
            ckv_kr[..., cfg.kv_lora_rank:][:, :, None, :],
            positions,
            cfg.rope_theta,
            cfg.rope_scaling,
        )  # [B, T, 1, dr]

        # The latent up-projection W_ukv as a RAW kernel: the absorbed
        # decode path contracts its W_uk / W_uv halves separately, so
        # both paths must read the same parameter.
        kv_b = self.param(
            "kv_b_kernel",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(),
                ("kv_latent", "q_heads", "head_dim"),
            ),
            (cfg.kv_lora_rank, h, dn + dv),
            cfg.param_dtype,
        )

        if cfg.decode:
            out = self._absorbed_cached_attention(
                q_nope, q_pe, c_kv, k_pe[:, :, 0, :], kv_b, segment_ids
            )
        else:
            kv = jnp.einsum(
                "btr,rhd->bthd",
                c_kv.astype(cfg.dtype),
                kv_b.astype(cfg.dtype),
            )
            k_nope, v = kv[..., :dn], kv[..., dn:]
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:3], dr))],
                axis=-1,
            )
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            q = nn.with_logical_constraint(
                q, ("batch", "act_seq", "act_heads", "head_dim")
            )
            k = nn.with_logical_constraint(
                k, ("batch", "act_seq", "act_heads", "head_dim")
            )
            v = nn.with_logical_constraint(
                v, ("batch", "act_seq", "act_heads", "head_dim")
            )
            # Scale is qk_head_dim**-0.5 everywhere — the backends
            # derive it from q's last dim, which IS qk_head_dim here.
            if cfg.attention_backend == "xla":
                out = multi_head_attention(
                    q, k, v, causal=True, segment_ids=segment_ids,
                    backend="xla",
                )
            elif cfg.attention_backend in ("flash", "ring", "ulysses"):
                # Zero-pad v to the qk head dim: softmax(QK^T) @ [v|0]
                # = [out|0], so slicing recovers the exact result; the
                # kernels then see ONE head dim everywhere (ulysses
                # additionally all-to-alls the padded head axis — the
                # decoupled-rope key is already broadcast per head, so
                # the exchange sees plain [B,T,H,D] tensors). Dispatch
                # through the shared entry point (ops.attention) so
                # backend plumbing can't drift per-model.
                v_pad = jnp.pad(
                    v, ((0, 0), (0, 0), (0, 0), (0, cfg.qk_head_dim - dv))
                )
                out = multi_head_attention(
                    q, k, v_pad, causal=True, segment_ids=segment_ids,
                    backend=cfg.attention_backend,
                )[..., :dv]
            else:
                raise NotImplementedError(
                    "MLA attention backends: 'xla', 'flash', 'ring', "
                    f"or 'ulysses'; got {cfg.attention_backend!r}"
                )
        return projection(
            cfg, out, cfg.d_model, (-2, -1),
            ("heads", "head_dim"), ("embed",), "o",
        )

    def _absorbed_cached_attention(
        self, q_nope, q_pe, c_kv, k_pe, kv_b, segment_ids
    ):
        """Decode with the latent cache and absorbed up-projections.

        The store (tpufw.ops.kv_store) holds ``c_kv`` [B, S, kvr] +
        roped ``k_pe`` [B, S, dr] (the MLA memory win). Scores: W_uk is
        folded into the query (``q_lat = q_nope @ W_uk``), so
        nope-scores contract in latent space; the output contracts
        attention-weighted latents with W_uv once.
        """
        cfg = self.cfg
        dn = cfg.qk_nope_head_dim
        read, seg, q_slots = kv_store.append(
            self, cfg, {"cached_ckv": c_kv, "cached_kpe": k_pe}, segment_ids
        )
        w_uk, w_uv = kv_b[..., :dn], kv_b[..., dn:]  # [kvr, H, dn/dv]
        # Absorb W_uk into the query: [B,T,H,dn] x [kvr,H,dn] -> latent
        # queries [B,T,H,kvr].
        q_lat = jnp.einsum(
            "bthd,rhd->bthr",
            q_nope.astype(cfg.dtype),
            w_uk.astype(cfg.dtype),
        )

        # ONE W_uv application, whatever the rungs.
        return jnp.einsum(
            "bthr,rhd->bthd",
            read(
                _AttendLatents(float(cfg.qk_head_dim) ** -0.5, cfg.dtype),
                (q_lat, q_pe.astype(cfg.dtype), seg, q_slots),
            ),
            w_uv.astype(cfg.dtype),
        )


@dataclasses.dataclass(frozen=True)
class _AttendLatents:
    """``kv_store.append``'s ``attend`` for the latent cache: attention-
    weighted latents [K,T,H,kvr] over the L slots of the K rows the store
    shows (their live prefix), for the same rows' queries. Hashable by
    value: the layers of a model share one trace of each branch."""

    scale: float
    dtype: Any

    def __call__(self, views, kv_seg, rows):
        ckv, kpe = views["cached_ckv"], views["cached_kpe"]
        q_lat, q_pe, seg, q_slots = rows
        logits = (
            jnp.einsum(
                "bthr,bsr->bhts", q_lat, ckv,
                preferred_element_type=jnp.float32,
            )
            + jnp.einsum(
                "bthd,bsd->bhts", q_pe, kpe,
                preferred_element_type=jnp.float32,
            )
        ) * self.scale
        mask = attention_mask(
            q_slots.shape[1], ckv.shape[1], segment_ids=seg,
            kv_segment_ids=kv_seg, q_positions=q_slots,
        )
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(self.dtype)
        return jnp.einsum("bhts,bsr->bthr", probs, ckv)


class DeepseekMoE(nn.Module):
    """DeepSeek MoE FFN: fine-grained routed experts (einsum dispatch,
    tpufw.models.mixtral.MoEMLP with the V2 gate conventions) plus
    always-on shared experts fused into one wide SwiGLU. Returns
    (y, aux_loss)."""

    cfg: DeepseekConfig

    @nn.compact
    def __call__(self, x, valid=None):
        cfg = self.cfg
        routed, aux = MoEMLP(
            cfg,
            d_ff=cfg.moe_d_ff,
            norm_topk=cfg.norm_topk_prob,
            group_limit=(
                (cfg.n_group, cfg.topk_group) if cfg.n_group else None
            ),
            scoring=cfg.moe_scoring,
            held=cfg.experts_held,
            name="routed",
        )(x, valid=valid)
        y = routed * cfg.routed_scaling_factor
        if cfg.n_shared_experts:
            y = y + MLP(
                cfg,
                d_ff=cfg.moe_d_ff * cfg.n_shared_experts,
                name="shared",
            )(x)
        return y, aux


class DeepseekBlock(nn.Module):
    cfg: DeepseekConfig

    def _layer_index(self) -> Optional[int]:
        """Unscanned layers are named ``layer_{i}`` by decoder_lm; the
        scanned stack shares one set of weights across layers and has
        no index (homogeneous by construction)."""
        name = self.name or ""
        if name.startswith("layer_"):
            return int(name.split("_", 1)[1])
        return None

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        attn_out = MLAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, name="attn_norm")(x), positions, segment_ids
        )
        x = x + checkpoint_name(attn_out, "attn_out")
        h = RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
        idx = self._layer_index()
        use_moe = cfg.moe and (idx is None or idx >= cfg.first_k_dense)
        if use_moe:
            y, aux = DeepseekMoE(cfg, name="moe")(
                h,
                valid=None if segment_ids is None else segment_ids > 0,
            )
        else:
            y, aux = MLP(cfg, name="mlp")(h), jnp.zeros((), jnp.float32)
        x = nn.with_logical_constraint(
            x + y, ("batch", "act_seq", "act_embed")
        )
        return (x, aux) if cfg.moe else x


class Deepseek(nn.Module):
    """Decoder-only DeepSeek-V2 LM (dense or MoE FFN). Returns logits,
    or (logits, aux_loss) for MoE configs when ``return_aux`` (the
    Mixtral contract — train_step adds aux into the objective)."""

    cfg: DeepseekConfig

    @nn.compact
    def __call__(
        self, tokens, positions=None, segment_ids=None, return_aux=True,
        return_hidden=False,
    ):
        cfg = self.cfg
        out = decoder_lm(
            cfg, DeepseekBlock, tokens, positions, segment_ids, cfg.moe,
            return_hidden=return_hidden,
        )
        if not cfg.moe:
            return out
        logits, aux = out
        if return_aux:
            return logits, aux / cfg.n_layers
        return logits


DEEPSEEK_CONFIGS: dict[str, DeepseekConfig] = {
    # Test-scale config (CPU mesh, parity tests).
    "deepseek_tiny": DeepseekConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        d_ff=128,
        max_seq_len=128,
        remat=False,
    ),
    # Same, exercising the compressed-q path (V2-236B style).
    "deepseek_tiny_qlora": DeepseekConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        q_lora_rank=24,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        d_ff=128,
        max_seq_len=128,
        remat=False,
    ),
    # MoE test preset: 4 fine-grained routed experts top-2 + 1 shared,
    # all-MoE (scan-compatible), V2 gate conventions.
    "deepseek_moe_tiny": DeepseekConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        d_ff=128,
        n_routed_experts=4,
        experts_per_token=2,
        moe_d_ff=48,
        n_shared_experts=1,
        capacity_factor=4.0,  # dropless at test scale
        max_seq_len=128,
        remat=False,
    ),
    # V2-Lite attention geometry (HF deepseek-ai/DeepSeek-V2-Lite:
    # d=2048, 16 heads, kv_lora 512, 128/64/128 head dims) with a dense
    # FFN sized to one v5e chip — NOT checkpoint-compatible with
    # V2-Lite (whose FFN is MoE and whose rope is yarn); it is the
    # bench shape for the MLA attention path.
    "deepseek_mla_bench": DeepseekConfig(
        vocab_size=32_768,
        d_model=2048,
        n_layers=10,
        n_heads=16,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        d_ff=6144,
        max_seq_len=4096,
        attention_backend="flash",
    ),
}
