"""Laguna family: a decoder whose layers repeat a period of one global
attention layer and three sliding-window layers, a leading dense layer
and then softmax-routed experts beside a shared one.

- **Attention**, both kinds through ``llama.Attention``: grouped-query
  over the model's K/V heads with a number of QUERY heads that is the
  layer's own (``heads_per_layer``), a sigmoid gate of one scalar a head
  on the heads' output, and a rotary embedding by layer kind: global
  layers YaRN over the first half of each head (``rope_full``), window
  layers plain rope over all of it (``rope_sliding``).
- **Caches** (``cfg.decode``): a global layer's keys and values live on
  the store's rows or page arena (``tpufw.ops.kv_store.append``, the
  ladder of ``max_seq_len``); a window layer keeps a ring of its last
  ``sliding_window`` keys per row (``ring_append``), whatever the
  context: per-slot in the pools, so the prefix trie, slot export and
  speculation decline this family (``tpufw.infer.slots.reject_state``).
- **Feed-forward**: ``mlp_layer_types`` says which layers are a dense
  SwiGLU (the leading one) and which ``deepseek.DeepseekMoE`` with
  softmax scoring, the top-k renormalised, a routed scaling factor and
  one shared expert, told which experts this chip holds
  (``experts_held``).

Layers differ in kind, so the trunk is never scanned.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpufw.models.deepseek import DeepseekMoE, YarnScaling
from tpufw.models.llama import (
    MLP,
    Attention,
    LayerRope,
    LlamaConfig,
    RMSNorm,
    decoder_lm,
)

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
#: The published pattern: every fourth layer global, from layer 0.
PERIOD = (FULL, SLIDING, SLIDING, SLIDING)
#: Laguna-S-2.1's ``rope_parameters``: the attention factor is taken as
#: published (0.1 ln 128 + 1), not derived.
ROPE_FULL = LayerRope(
    theta=500_000.0,
    scaling=YarnScaling(
        factor=128.0,
        original_max_position_embeddings=8192,
        beta_fast=32.0,
        beta_slow=1.0,
        attention_factor=1.4852030263919618,
    ),
    rotary_dim=64,
)
ROPE_SLIDING = LayerRope(theta=10_000.0)


@dataclasses.dataclass(frozen=True)
class LagunaConfig(LlamaConfig):
    """LlamaConfig's fields describe the trunk, the K/V heads and the
    dense layer (``d_ff``); ``n_heads`` is unused where
    ``heads_per_layer`` names each layer's own."""

    #: The family's constant, not a field: with ``cfg.decode`` a window
    #: layer keeps a ring of its last ``sliding_window`` keys per row
    #: (``llama.Attention`` reads it; 512 of a 1M context in 36 of 48
    #: layers).
    window_ring: ClassVar[bool] = True

    vocab_size: int = 100_352
    d_model: int = 3072
    n_layers: int = 48
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 12_288
    rms_eps: float = 1e-6
    max_seq_len: int = 16_384
    scan_layers: bool = False
    #: Kind of each layer's attention and feed-forward, and its query
    #: heads: each ``n_layers`` long.
    layer_types: tuple = PERIOD * 12
    mlp_layer_types: tuple = (DENSE,) + (SPARSE,) * 47
    heads_per_layer: tuple = (48, 72, 72, 72) * 12
    #: The window layers' window, in keys (this token included).
    sliding_window: Optional[int] = 512
    rope_full: LayerRope = ROPE_FULL
    rope_sliding: LayerRope = ROPE_SLIDING
    attn_output_gate: str = "per_head"
    # --- expert layers (the field names deepseek.DeepseekMoE reads) ---
    n_routed_experts: int = 256
    experts_per_token: int = 10
    moe_d_ff: int = 1024
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    moe_scoring: str = "softmax"
    #: (first, n): the routed experts this chip holds of each layer;
    #: None = all of them.
    experts_held: Optional[tuple] = None
    n_group: int = 0
    topk_group: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.0
    router_z_weight: float = 0.0
    moe_dispatch: str = "sorted"

    @property
    def n_experts(self) -> int:
        """Alias: tpufw.models.mixtral.MoEMLP reads ``cfg.n_experts``
        (the router's width)."""
        return self.n_routed_experts

    def __post_init__(self):
        if self.scan_layers:
            raise ValueError(
                "laguna layers differ in kind — nn.scan needs "
                "homogeneous layers; keep scan_layers=False"
            )
        for name, kinds in (
            ("layer_types", (FULL, SLIDING)),
            ("mlp_layer_types", (DENSE, SPARSE)),
        ):
            got = getattr(self, name)
            if len(got) != self.n_layers or any(k not in kinds for k in got):
                raise ValueError(
                    f"{name} must name one of {kinds} for each of the "
                    f"{self.n_layers} layers, got {got!r}"
                )
        if len(self.heads_per_layer) != self.n_layers or any(
            h % self.n_kv_heads for h in self.heads_per_layer
        ):
            raise ValueError(
                f"heads_per_layer must give each of the {self.n_layers} "
                f"layers a multiple of n_kv_heads={self.n_kv_heads}, got "
                f"{self.heads_per_layer!r}"
            )

    def n_params(self, include_embed: bool = True) -> int:
        d, hd = self.d_model, self.head_dim
        held = (
            self.n_routed_experts
            if self.experts_held is None else self.experts_held[1]
        )
        moe = (
            3 * d * self.moe_d_ff * (held + self.n_shared_experts)
            + d * self.n_routed_experts
        )
        total = d
        for h, mlp in zip(self.heads_per_layer, self.mlp_layer_types):
            total += 2 * d * h * hd + 2 * d * self.n_kv_heads * hd + d * h
            total += 2 * d + (3 * d * self.d_ff if mlp == DENSE else moe)
        if include_embed:
            total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return total


class LagunaBlock(nn.Module):
    cfg: LagunaConfig

    def _index(self) -> int:
        """decoder_lm names unscanned layers ``layer_{i}``."""
        return int(self.name.split("_", 1)[1])

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg, i = self.cfg, self._index()
        sliding = cfg.layer_types[i] == SLIDING
        h = RMSNorm(cfg.rms_eps, name="attn_norm")(x)
        with jax.named_scope("attn_window" if sliding else "attn_global"):
            mix = Attention(
                cfg,
                window=cfg.sliding_window if sliding else None,
                n_heads=cfg.heads_per_layer[i],
                rope=cfg.rope_sliding if sliding else cfg.rope_full,
                name="attn",
            )(h, positions, segment_ids)
        x = x + mix
        h = RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
        if cfg.mlp_layer_types[i] == DENSE:
            y, aux = MLP(cfg, name="mlp")(h), jnp.zeros((), jnp.float32)
        else:
            with jax.named_scope("moe_share"):
                y, aux = DeepseekMoE(cfg, name="moe")(
                    h,
                    valid=None if segment_ids is None else segment_ids > 0,
                )
        x = nn.with_logical_constraint(
            x + y, ("batch", "act_seq", "act_embed")
        )
        return x, aux


class Laguna(nn.Module):
    """Decoder-only LM. Returns (logits, aux_loss) when ``return_aux``
    else logits (the Mixtral contract)."""

    cfg: LagunaConfig

    @nn.compact
    def __call__(
        self, tokens, positions=None, segment_ids=None, return_aux=True,
        return_hidden=False,
    ):
        cfg = self.cfg
        logits, aux = decoder_lm(
            cfg, LagunaBlock, tokens, positions, segment_ids, True,
            return_hidden=return_hidden,
        )
        if return_aux:
            return logits, aux / cfg.n_layers
        return logits


LAGUNA_CONFIGS: dict[str, LagunaConfig] = {
    # Test scale: two periods, a window of 16 under a context of 128,
    # head counts 4 and 6 over 2 K/V heads, half-rotary YaRN on global
    # layers, 8 of 16 experts held, top-2.
    "laguna_tiny": LagunaConfig(
        vocab_size=256,
        d_model=64,
        n_layers=8,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        max_seq_len=128,
        remat=False,
        layer_types=PERIOD * 2,
        mlp_layer_types=(DENSE,) + (SPARSE,) * 7,
        heads_per_layer=(4, 6, 6, 6) * 2,
        sliding_window=16,
        rope_full=LayerRope(
            theta=500_000.0,
            scaling=YarnScaling(
                factor=8.0,
                original_max_position_embeddings=32,
                attention_factor=1.2079441541679836,
            ),
            rotary_dim=8,
        ),
        n_routed_experts=16,
        experts_per_token=2,
        moe_d_ff=32,
        experts_held=(0, 8),
        capacity_factor=8.0,
    ),
}
