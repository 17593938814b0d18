"""Olmo-Hybrid family: a dense decoder whose layers repeat a period of
three linear-attention layers and one softmax layer, every layer's
mixer and MLP normalised AFTER they run (OLMo 2's block):

    h = x + RMSNorm(Mixer(x))
    y = h + RMSNorm(MLP(h))

- **Linear-attention layer** (``"linear_attention"``; Gated DeltaNet,
  arXiv:2412.06464): q, k, v through a depthwise causal convolution and
  SiLU, q and k L2-normalised per head, ONE decay a head and a write
  strength in (0, 2) through the gated delta rule (``tpufw.ops.kda``,
  its scalar-decay form), a per-head RMSNorm and a SiLU gate on the way
  out. d_k != d_v: per head a [d_k, d_v] float32 state, cache leaf
  ``gdn_state``, beside the convolution's last ``kernel - 1`` inputs in
  ``conv_state``; per-slot STATE of ``tpufw.ops.kv_store``.
- **Softmax layer** (``"full_attention"``): multi-head attention with NO
  position signal (``use_rope=False``: position reaches it through the
  linear layers' recurrence and convolution) and an RMSNorm over the
  whole projected q and k (``qk_norm``), through ``llama.Attention`` and
  its cache code.

Layers differ in kind but periods do not: the trunk's unit is the PERIOD
(``OlmoHybridPeriod``, as Gemma's is the pair), scanned or unrolled with
the same parameters under ``layers`` / ``layer_{p}``. Serving only: the
chunkwise delta rule has a forward pass and no tested backward.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpufw.models.llama import (
    MLP,
    Attention,
    LlamaConfig,
    RMSNorm,
    decoder_lm,
    projection,
)
from tpufw.models.solar_open2 import raw_param, short_conv
from tpufw.ops import kv_store, rms_norm
from tpufw.ops.kda import decay_rate, kda_chunk, kda_step, unit_qk

LAYER_KINDS = ("linear_attention", "full_attention")
#: The published pattern: every fourth layer softmax, from layer 3.
PERIOD = ("linear_attention",) * 3 + ("full_attention",)
#: The recurrent state's type. Not a setting: a probe that wants to see
#: what a narrower state costs rebinds this name before it builds.
GDN_STATE_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(LlamaConfig):
    """LlamaConfig's fields describe the softmax layers (n_heads,
    n_kv_heads, head_dim), the dense MLP (``d_ff``) and the trunk; the
    defaults are Olmo-Hybrid-7B's."""

    vocab_size: int = 100_352
    d_model: int = 3840
    n_layers: int = 32
    n_heads: int = 30
    n_kv_heads: int = 30
    head_dim: int = 128
    d_ff: int = 11_008
    rms_eps: float = 1e-6
    max_seq_len: int = 65_536
    #: Kind of each layer, ``n_layers`` long: whole periods.
    layer_types: tuple = PERIOD * 8
    use_rope: bool = False
    qk_norm: bool = True
    # --- linear-attention layers ---
    gdn_heads: int = 30
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    gdn_conv: int = 4
    #: beta in (0, 2) rather than (0, 1): the transition may reflect.
    gdn_neg_eigval: bool = True

    @property
    def kv_store_heads(self) -> int:
        """K/V heads a slot of the store holds: the model's, rounded up
        to whole tiles of 8 sublanes (30 -> 32, two of zeros). The same
        bytes in HBM, and no second copy of the arena in the decode
        programs (``kv_store._stored_heads``, which reads this)."""
        return -(-self.n_kv_heads // 8) * 8

    @property
    def period(self) -> tuple:
        """The kinds of one period: up to and with the first softmax
        layer."""
        return self.layer_types[: self.layer_types.index("full_attention") + 1]

    def check_layers(self) -> None:
        """Called where the model is built, not in ``__post_init__``:
        the trunk's own config counts periods in ``n_layers``."""
        kinds = tuple(self.layer_types)
        if (
            len(kinds) != self.n_layers
            or any(k not in LAYER_KINDS for k in kinds)
            or "full_attention" not in kinds
            or kinds != self.period * (len(kinds) // len(self.period))
        ):
            raise ValueError(
                f"layer_types must be whole periods of {LAYER_KINDS}, one "
                f"kind for each of the {self.n_layers} layers, got {kinds!r}"
            )

    def n_params(self, include_embed: bool = True) -> int:
        d, h = self.d_model, self.gdn_heads
        ck, cv = h * self.gdn_key_dim, h * self.gdn_value_dim
        full = (
            2 * d * self.n_heads * self.head_dim
            + 2 * d * self.n_kv_heads * self.head_dim
            + (self.n_heads + self.n_kv_heads) * self.head_dim
        )
        linear = (
            d * (2 * ck + 2 * cv + 2 * h)
            + cv * d
            + self.gdn_conv * (2 * ck + cv)
            + 2 * h
            + self.gdn_value_dim
        )
        body = 3 * d * self.d_ff + 2 * d
        total = d + sum(
            (full if k == "full_attention" else linear) + body
            for k in self.layer_types
        )
        if include_embed:
            total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return total


class GatedDeltaNetLayer(nn.Module):
    """One linear-attention mixer. x [B,T,d] -> [B,T,d]; positions play
    no part. With ``cfg.decode`` the state and the convolution's tail
    live in the "cache" collection and every call continues from them:
    T > 1 runs the chunkwise rule (prefill, whole or in chunks), T == 1
    the one-step rule (decode). ``segment_ids == 0`` marks padding and a
    pool's done rows, which leave both exactly as they were."""

    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg = self.cfg
        b, t, _ = x.shape
        h, dk, dv = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
        f32 = jnp.float32
        valid = None if segment_ids is None else segment_ids > 0

        def heads(name, width):
            return projection(
                cfg, x, (h, width), -1,
                ("embed",), ("q_heads", "head_dim"), name,
            ).reshape(b, t, h * width)

        def per_head(name):
            return projection(
                cfg, x, h, -1, ("embed",), ("q_heads",), name
            ).astype(f32)

        with jax.named_scope("gdn_conv"):
            q, k, v = short_conv(
                self, cfg, [heads("q", dk), heads("k", dk), heads("v", dv)],
                cfg.gdn_conv, valid,
            )
        q, k = unit_qk(
            q.reshape(b, t, h, dk).astype(f32),
            k.reshape(b, t, h, dk).astype(f32),
        )
        v = v.reshape(b, t, h, dv).astype(f32)
        if cfg.decode:
            state = kv_store.slot_state(
                self, "gdn_state", (b, h, dk, dv), GDN_STATE_DTYPE
            )
            s0 = state.value
        else:
            s0 = jnp.zeros((b, h, dk, dv), GDN_STATE_DTYPE)

        zeros = nn.initializers.zeros_init()
        a_log = raw_param(self, cfg, "A_log", (h,), zeros).astype(f32)
        dt_bias = raw_param(self, cfg, "dt_bias", (h,), zeros).astype(f32)
        g = decay_rate(a_log, False) * jax.nn.softplus(
            per_head("decay") + dt_bias
        )
        beta = nn.sigmoid(per_head("beta")) * (
            2.0 if cfg.gdn_neg_eigval else 1.0
        )

        if cfg.decode and t == 1:
            with jax.named_scope("gdn_step"):
                if valid is not None:
                    # Padding is the identity: alpha = 1, beta = 0.
                    g = jnp.where(valid[:, :, None], g, 0.0)
                    beta = jnp.where(valid[:, :, None], beta, 0.0)
                o, s1 = kda_step(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0
                )
                o = o[:, None]
        else:
            with jax.named_scope("gdn_chunk"):
                o, s1 = kda_chunk(q, k, v, g, beta, s0, valid)
        if cfg.decode:
            state.value = s1

        scale = self.param(
            "o_norm",
            nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("norm",)
            ),
            (dv,),
            f32,
        )
        # A flat [d, H * dv] kernel, so the int8 path's table reads it
        # like an MLP's ``gate``.
        gate = projection(
            cfg, x, h * dv, -1, ("embed",), ("heads",), "gate"
        ).astype(f32)
        o = rms_norm(o, scale, cfg.rms_eps) * nn.silu(gate).reshape(o.shape)
        return projection(
            cfg, o.astype(cfg.dtype), cfg.d_model, (-2, -1),
            ("heads", "head_dim"), ("embed",), "o",
        )


class OlmoHybridBlock(nn.Module):
    """One layer of ``kind``: the mixer, then the MLP, each normalised
    AFTER it runs and before it joins the residual."""

    cfg: OlmoHybridConfig
    kind: str = "linear_attention"

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        if self.kind == "full_attention":
            with jax.named_scope("attn_full"):
                mix = Attention(cfg, name="attn")(x, positions, segment_ids)
        else:
            mix = GatedDeltaNetLayer(cfg, name="gdn")(x, segment_ids)
        x = x + RMSNorm(cfg.rms_eps, name="mixer_norm")(mix)
        with jax.named_scope("mlp_dense"):
            y = MLP(cfg, name="mlp")(x)
        x = x + RMSNorm(cfg.rms_eps, name="mlp_norm")(y)
        return nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))


class OlmoHybridPeriod(nn.Module):
    """The trunk's unit: one block of each kind of ``cfg.period``, in
    order (``linear_0``, ``linear_1``, ``linear_2``, ``full_3``)."""

    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        for j, kind in enumerate(self.cfg.period):
            x = OlmoHybridBlock(
                self.cfg, kind=kind, name=f"{kind.split('_')[0]}_{j}"
            )(x, positions, segment_ids)
        return x


class OlmoHybrid(nn.Module):
    """Decoder-only hybrid LM. Returns logits [B, T, vocab]."""

    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(
        self, tokens, positions=None, segment_ids=None, return_hidden=False
    ):
        cfg = self.cfg
        cfg.check_layers()
        # The trunk runs periods: n_layers counts them there (Gemma's
        # pairs likewise).
        trunk_cfg = dataclasses.replace(
            cfg, n_layers=cfg.n_layers // len(cfg.period)
        )
        return decoder_lm(
            trunk_cfg, OlmoHybridPeriod, tokens, positions, segment_ids,
            False, return_hidden=return_hidden,
        )


OLMO_HYBRID_CONFIGS: dict[str, OlmoHybridConfig] = {
    # Test scale: two periods, d_k != d_v, neither a power of two.
    "olmo_hybrid_tiny": OlmoHybridConfig(
        vocab_size=256,
        d_model=64,
        n_layers=8,
        n_heads=4,
        n_kv_heads=4,
        head_dim=16,
        d_ff=128,
        max_seq_len=128,
        remat=False,
        layer_types=PERIOD * 2,
        gdn_heads=4,
        gdn_key_dim=12,
        gdn_value_dim=24,
    ),
}
