"""Solar-Open2 family: a hybrid decoder whose layers repeat a period of
one softmax layer and three linear-attention layers, every layer
followed by sigmoid-routed experts beside a shared one.

- **Softmax layer** (``"gqa"``): grouped-query attention with NO
  position signal (``use_rope=False``) and an elementwise sigmoid gate
  on the heads' output, through ``llama.Attention`` and its cache code
  (contiguous rows and the paged arena alike).
- **Linear-attention layer** (``"kda"``; Kimi Delta Attention,
  arXiv:2510.26692): q, k, v through a depthwise causal convolution and
  SiLU, q and k L2-normalised per head, a per-channel decay and a write
  strength in (0, 2) through the gated delta rule (``tpufw.ops.kda``),
  a gated per-head RMSNorm on the way out. What a row keeps between
  calls is NOT keys and values: per head a [d_k, d_v] float32 state and
  the convolution's last ``kernel - 1`` inputs, cache leaves
  ``kda_state`` and ``conv_state`` with the batch axis first. The pools
  carry them as per-slot state (``tpufw.ops.kv_store``: role STATE).
- **Expert layer**: ``deepseek.DeepseekMoE`` with sigmoid scoring and a
  selection bias (``tpufw.ops.moe``), told which experts this chip
  holds (``experts_held``).

Layers differ in kind, so the trunk is never scanned. Serving only: the
chunkwise delta rule has a forward pass and no tested backward.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpufw.models.deepseek import DeepseekMoE
from tpufw.models.llama import (
    Attention,
    LlamaConfig,
    RMSNorm,
    decoder_lm,
    projection,
)
from tpufw.ops import kv_store, rms_norm
from tpufw.ops.kda import (
    causal_conv,
    decay_rate,
    kda_chunk,
    kda_step,
    unit_qk,
)

LAYER_KINDS = ("gqa", "kda")
#: The recurrent state's type. Not a setting: a probe that wants to see
#: what a narrower state costs rebinds this name before it builds.
KDA_STATE_DTYPE = jnp.float32
#: The published pattern: every fourth layer softmax, from layer 0.
PERIOD = ("gqa", "kda", "kda", "kda")


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config(LlamaConfig):
    """LlamaConfig's fields describe the softmax layers (n_heads,
    n_kv_heads, head_dim) and the trunk; ``d_ff`` is unused (no dense
    layer: ``first_k_dense_replace`` is 0)."""

    n_heads: int = 64
    n_kv_heads: int = 8
    rms_eps: float = 1e-5
    scan_layers: bool = False
    #: Kind of each layer, ``n_layers`` long.
    layer_types: tuple = PERIOD * 8
    use_rope: bool = False
    attn_output_gate: bool = True
    # --- linear-attention layers ---
    kda_heads: int = 64
    kda_head_dim: int = 128
    kda_conv: int = 4
    #: Rank of the decay's and the output gate's low-rank projections.
    kda_rank: int = 128
    #: beta in (0, 2) rather than (0, 1): the transition may reflect.
    kda_neg_eigval: bool = True
    # --- expert layers (the field names deepseek.DeepseekMoE reads) ---
    n_routed_experts: int = 320
    experts_per_token: int = 8
    moe_d_ff: int = 1280
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    moe_scoring: str = "sigmoid"
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
                "solar_open2 layers differ in kind — nn.scan needs "
                "homogeneous layers; keep scan_layers=False"
            )
        if len(self.layer_types) != self.n_layers or any(
            k not in LAYER_KINDS for k in self.layer_types
        ):
            raise ValueError(
                f"layer_types must name one of {LAYER_KINDS} for each of "
                f"the {self.n_layers} layers, got {self.layer_types!r}"
            )

    def n_params(self, include_embed: bool = True) -> int:
        d, hd = self.d_model, self.head_dim
        c = self.kda_heads * self.kda_head_dim
        gqa = (
            2 * d * self.n_heads * hd
            + 2 * d * self.n_kv_heads * hd
            + d * self.n_heads * hd
            + 2 * d
        )
        kda = (
            4 * d * c
            + 2 * (d * self.kda_rank + self.kda_rank * c)
            + d * self.kda_heads
            + 3 * self.kda_conv * c
            + self.kda_heads + c + self.kda_head_dim
            + 2 * d
        )
        held = (
            self.n_routed_experts
            if self.experts_held is None else self.experts_held[1]
        )
        moe = (
            3 * d * self.moe_d_ff * (held + self.n_shared_experts)
            + (d + 1) * self.n_routed_experts
        )
        total = d + sum(
            (gqa if k == "gqa" else kda) + moe for k in self.layer_types
        )
        if include_embed:
            total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return total


def raw_param(module, cfg, name, shape, init):
    """A parameter that is no projection's kernel: unpartitioned, in the
    parameters' type."""
    return module.param(
        name,
        nn.with_logical_partitioning(init, (None,) * len(shape)),
        shape,
        cfg.param_dtype,
    )


def short_conv(module, cfg, parts, kernel: int, valid):
    """The depthwise causal convolution (no bias) and SiLU in front of a
    delta-rule layer's q, k and v. ``parts`` [B,T,c_i] each, in that
    order; one kernel a part (``q_conv``, ``k_conv``, ``v_conv``), one
    ``conv_state`` leaf for the tail of all three, which with
    ``cfg.decode`` this call continues from and writes. Returns the
    three convolved parts."""
    mixed = jnp.concatenate(parts, -1)
    b, _, c = mixed.shape
    conv_w = jnp.concatenate(
        [
            raw_param(
                module, cfg, f"{n}_conv", (kernel, part.shape[-1]),
                nn.initializers.lecun_normal(),
            )
            for n, part in zip("qkv", parts)
        ],
        axis=-1,
    )
    if cfg.decode:
        tail = kv_store.slot_state(
            module, "conv_state", (b, kernel - 1, c), cfg.dtype
        )
        tail0 = tail.value
    else:
        tail0 = jnp.zeros((b, kernel - 1, c), cfg.dtype)
    mixed, tail1 = causal_conv(mixed, conv_w, tail0, valid)
    if cfg.decode:
        tail.value = tail1
    k_at = parts[0].shape[-1]
    return jnp.split(
        nn.silu(mixed), [k_at, k_at + parts[1].shape[-1]], axis=-1
    )


class KDALayer(nn.Module):
    """One linear-attention mixer. x [B,T,d] -> [B,T,d]; positions play
    no part. With ``cfg.decode`` the state and the convolution's tail
    live in the "cache" collection and every call continues from them:
    T > 1 runs the chunkwise rule (prefill, whole or in chunks), T == 1
    the one-step rule (decode). ``segment_ids == 0`` marks padding,
    which leaves both exactly as they were."""

    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg = self.cfg
        b, t, _ = x.shape
        h, dk = cfg.kda_heads, cfg.kda_head_dim
        c = h * dk
        f32 = jnp.float32
        valid = None if segment_ids is None else segment_ids > 0

        def heads(name):
            return projection(
                cfg, x, (h, dk), -1,
                ("embed",), ("q_heads", "head_dim"), name,
            ).reshape(b, t, c)

        def low_rank(a, bb, out_name):
            mid = projection(
                cfg, x, cfg.kda_rank, -1, ("embed",), ("lora",), a
            )
            return projection(
                cfg, mid, c, -1, ("lora",), (out_name,), bb
            )

        q, k, v = (
            a.reshape(b, t, h, dk).astype(f32)
            for a in short_conv(
                self, cfg, [heads("q"), heads("k"), heads("v")],
                cfg.kda_conv, valid,
            )
        )
        if cfg.decode:
            state = kv_store.slot_state(
                self, "kda_state", (b, h, dk, dk), KDA_STATE_DTYPE
            )
            s0 = state.value
        else:
            s0 = jnp.zeros((b, h, dk, dk), KDA_STATE_DTYPE)
        q, k = unit_qk(q, k)

        zeros = nn.initializers.zeros_init()
        a_log = raw_param(self, cfg, "A_log", (h,), zeros).astype(f32)
        dt_bias = raw_param(self, cfg, "dt_bias", (c,), zeros)
        g = decay_rate(a_log, True) * jax.nn.softplus(
            (low_rank("f_a", "f_b", "heads").astype(f32)
             + dt_bias.astype(f32)).reshape(b, t, h, dk)
        )
        beta = nn.sigmoid(
            projection(
                cfg, x, h, -1, ("embed",), ("q_heads",), "beta"
            ).astype(f32)
        ) * (2.0 if cfg.kda_neg_eigval else 1.0)

        if cfg.decode and t == 1:
            with jax.named_scope("kda_step"):
                if valid is not None:
                    # Padding is the identity: alpha = 1, beta = 0.
                    g = jnp.where(valid[:, :, None, None], g, 0.0)
                    beta = jnp.where(valid[:, :, None], beta, 0.0)
                o, s1 = kda_step(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0
                )
                o = o[:, None]
        else:
            with jax.named_scope("kda_chunk"):
                o, s1 = kda_chunk(q, k, v, g, beta, s0, valid)
        if cfg.decode:
            state.value = s1

        scale = self.param(
            "o_norm",
            nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("norm",)
            ),
            (dk,),
            f32,
        )
        gate = nn.sigmoid(low_rank("g_a", "g_b", "heads").astype(f32))
        o = rms_norm(o, scale, cfg.rms_eps) * gate.reshape(b, t, h, dk)
        return projection(
            cfg, o.astype(cfg.dtype), cfg.d_model, (-2, -1),
            ("heads", "head_dim"), ("embed",), "o",
        )


class SolarOpen2Block(nn.Module):
    cfg: SolarOpen2Config

    def _kind(self) -> str:
        """decoder_lm names unscanned layers ``layer_{i}``."""
        return self.cfg.layer_types[int(self.name.split("_", 1)[1])]

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_eps, name="attn_norm")(x)
        if self._kind() == "gqa":
            with jax.named_scope("gqa_gated"):
                mix = Attention(cfg, name="attn")(h, positions, segment_ids)
        else:
            mix = KDALayer(cfg, name="kda")(h, segment_ids)
        x = x + mix
        with jax.named_scope("moe_share"):
            y, aux = DeepseekMoE(cfg, name="moe")(
                RMSNorm(cfg.rms_eps, name="mlp_norm")(x),
                valid=None if segment_ids is None else segment_ids > 0,
            )
        x = nn.with_logical_constraint(
            x + y, ("batch", "act_seq", "act_embed")
        )
        return x, aux


class SolarOpen2(nn.Module):
    """Decoder-only hybrid LM. Returns (logits, aux_loss) when
    ``return_aux`` else logits (the Mixtral contract)."""

    cfg: SolarOpen2Config

    @nn.compact
    def __call__(
        self, tokens, positions=None, segment_ids=None, return_aux=True,
        return_hidden=False,
    ):
        cfg = self.cfg
        logits, aux = decoder_lm(
            cfg, SolarOpen2Block, tokens, positions, segment_ids, True,
            return_hidden=return_hidden,
        )
        if return_aux:
            return logits, aux / cfg.n_layers
        return logits


SOLAR_OPEN2_CONFIGS: dict[str, SolarOpen2Config] = {
    # Test scale: one period, 8 of 16 experts held, top-2.
    "solar_open2_tiny": SolarOpen2Config(
        vocab_size=256,
        d_model=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        max_seq_len=128,
        remat=False,
        layer_types=PERIOD,
        kda_heads=4,
        kda_head_dim=16,
        kda_rank=16,
        n_routed_experts=16,
        experts_per_token=2,
        moe_d_ff=32,
        experts_held=(0, 8),
        capacity_factor=8.0,
    ),
}
