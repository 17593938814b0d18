"""Falcon-H1 family: a decoder whose EVERY layer runs a Mamba-2 mixer and
grouped-query attention side by side on one normed input, adds both to
the residual, and follows with a dense SwiGLU MLP (arXiv:2507.22448).

    u = RMSNorm(h)
    h = h + SSM(u * ssm_in) * ssm_out + ATT(u * attn_in) * attn_out
    h = h + MLP(RMSNorm(h))

- **SSM** (``SSMMixer``): one projection to a gate z, the convolved
  stream xBC and a time step dt a head; a depthwise causal convolution
  with bias and SiLU on xBC; the state-space recurrence of
  ``tpufw.ops.ssd`` (a scalar decay a head, B and C shared by groups of
  heads, a ``D`` skip); the gate FIRST, then an RMSNorm over groups of
  channels, then the output projection. What a row keeps between calls
  is per head a [P, N] float32 state and the convolution's last
  ``kernel - 1`` inputs: cache leaves ``ssm_state`` and ``conv_state``,
  per-slot STATE of ``tpufw.ops.kv_store``.
- **ATT**: ``llama.Attention`` and its cache code (contiguous rows and
  the paged arena alike), with the config's multiplier on the keys.
- Every layer therefore holds BOTH kinds of cache, a page pair and
  per-slot state; layers are alike, so the trunk scans.

The multipliers (muP: on the embedding, the logits, the keys, both
mixers' inputs and outputs, the MLP's gate and output, and by column
group on the SSM's projection) are configuration fields applied at run
time where the equations show them; none is folded into a weight.
Serving only: the chunkwise recurrence has a forward pass and no tested
backward.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpufw.models.llama import (
    MLP,
    Attention,
    LlamaConfig,
    RMSNorm,
    decoder_lm,
    projection,
)
from tpufw.ops import kv_store, rms_norm
from tpufw.ops.kda import causal_conv
from tpufw.ops.ssd import ssd_chunk, ssd_step

#: The recurrent state's type. Not a setting: a probe that wants to see
#: what a narrower state costs rebinds this name before it builds.
SSM_STATE_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class FalconH1Config(LlamaConfig):
    """LlamaConfig's fields describe the attention heads, the dense MLP
    (``d_ff``) and the trunk; the defaults are Falcon-H1-34B's."""

    vocab_size: int = 261_120
    d_model: int = 5120
    n_layers: int = 72
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 21_504
    rope_theta: float = 1e11
    rms_eps: float = 1e-5
    max_seq_len: int = 262_144
    # --- the Mamba-2 mixer ---
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_state: int = 256
    #: Groups of heads that share B and C, and of channels the output
    #: norm normalises together.
    ssm_groups: int = 2
    ssm_conv: int = 4
    #: Positions per block of the chunkwise recurrence.
    ssm_chunk: int = 128
    # --- multipliers, each applied where the module docstring shows ---
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    #: On the gate's pre-activation and on the MLP's output.
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    #: On the SSM projection's columns, by group: z, x, B, C, dt.
    ssm_multipliers: tuple = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738,
    )

    def __post_init__(self):
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"ssm_groups={self.ssm_groups} must divide "
                f"ssm_heads={self.ssm_heads}"
            )

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the convolution covers: x, then B and C a group."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def n_params(self, include_embed: bool = True) -> int:
        d, c = self.d_model, self.ssm_conv_dim
        attn = (
            2 * d * self.n_heads * self.head_dim
            + 2 * d * self.n_kv_heads * self.head_dim
        )
        ssm = (
            d * (self.ssm_inner + c + self.ssm_heads)
            + self.ssm_inner * d
            + (self.ssm_conv + 1) * c
            + 3 * self.ssm_heads
            + self.ssm_inner
        )
        layer = attn + ssm + 3 * d * self.d_ff + 2 * d
        total = d + self.n_layers * layer
        if include_embed:
            total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return total


class SSMMixer(nn.Module):
    """One Mamba-2 mixer. x [B,T,d] -> [B,T,d]; positions play no part.
    With ``cfg.decode`` the state and the convolution's tail live in the
    "cache" collection and every call continues from them: T > 1 runs
    the chunkwise recurrence (prefill, whole or in chunks), T == 1 the
    one-step one (decode). ``segment_ids == 0`` marks padding and a
    pool's done rows, which leave both exactly as they were."""

    cfg: FalconH1Config

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg = self.cfg
        b, t, _ = x.shape
        h, p, n, g = (
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
        )
        inner, c, km1 = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_conv - 1
        f32 = jnp.float32
        valid = None if segment_ids is None else segment_ids > 0

        def raw(name, shape, init):
            return self.param(
                name,
                nn.with_logical_partitioning(init, (None,) * len(shape)),
                shape,
                cfg.param_dtype,
            )

        mz, mx, mb, mc, mdt = cfg.ssm_multipliers
        mup = np.concatenate([
            np.full(inner, mz), np.full(inner, mx), np.full(g * n, mb),
            np.full(g * n, mc), np.full(h, mdt),
        ]).astype(np.float32)
        proj = projection(
            cfg, x, inner + c + h, -1, ("embed",), ("mlp",), "in_proj"
        )
        proj = proj * jnp.asarray(mup, proj.dtype)
        z, xbc, dt = jnp.split(proj, [inner, inner + c], axis=-1)

        conv_w = raw("conv", (km1 + 1, c), nn.initializers.lecun_normal())
        conv_b = raw("conv_bias", (c,), nn.initializers.zeros_init())
        if cfg.decode:
            tail = kv_store.slot_state(
                self, "conv_state", (b, km1, c), cfg.dtype
            )
            state = kv_store.slot_state(
                self, "ssm_state", (b, h, p, n), SSM_STATE_DTYPE
            )
            tail0, s0 = tail.value, state.value
        else:
            tail0 = jnp.zeros((b, km1, c), cfg.dtype)
            s0 = jnp.zeros((b, h, p, n), SSM_STATE_DTYPE)
        with jax.named_scope("ssm_conv"):
            xbc, tail1 = causal_conv(xbc, conv_w, tail0, valid)
            xbc = nn.silu(xbc + conv_b.astype(xbc.dtype))
        xs, b_in, c_in = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        xs = xs.reshape(b, t, h, p)
        b_in, c_in = b_in.reshape(b, t, g, n), c_in.reshape(b, t, g, n)

        a_rate = jnp.exp(
            raw("A_log", (h,), nn.initializers.zeros_init()).astype(f32)
        )
        dt_bias = raw("dt_bias", (h,), nn.initializers.zeros_init())
        d_skip = raw("D", (h,), nn.initializers.ones_init())
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))

        if cfg.decode and t == 1:
            with jax.named_scope("ssd_step"):
                if valid is not None:
                    # Padding is the identity: a = 1, nothing written.
                    dt = jnp.where(valid[:, :, None], dt, 0.0)
                y, s1 = ssd_step(
                    xs[:, 0], dt[:, 0], a_rate, b_in[:, 0], c_in[:, 0],
                    d_skip, s0,
                )
                y = y[:, None]
        else:
            with jax.named_scope("ssd_chunk"):
                y, s1 = ssd_chunk(
                    xs, dt, a_rate, b_in, c_in, d_skip, s0, valid,
                    block=cfg.ssm_chunk,
                )
        if cfg.decode:
            tail.value, state.value = tail1, s1

        # The gate first, then the norm over each group's channels.
        y = y.reshape(b, t, inner) * nn.silu(z.astype(f32))
        scale = self.param(
            "norm",
            nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("norm",)
            ),
            (inner,),
            f32,
        )
        y = rms_norm(
            y.reshape(b, t, g, inner // g), scale.reshape(g, inner // g),
            cfg.rms_eps,
        ).reshape(b, t, inner)
        return projection(
            cfg, y.astype(cfg.dtype), cfg.d_model, -1,
            ("mlp",), ("embed",), "out_proj",
        )


def _scaled(x, multiplier: float):
    return x if multiplier == 1.0 else x * jnp.asarray(multiplier, x.dtype)


class FalconH1Block(nn.Module):
    cfg: FalconH1Config

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        u = RMSNorm(cfg.rms_eps, name="attn_norm")(x)
        ssm = SSMMixer(cfg, name="ssm")(
            _scaled(u, cfg.ssm_in_multiplier), segment_ids
        )
        with jax.named_scope("attn_parallel"):
            att = Attention(cfg, name="attn")(
                _scaled(u, cfg.attention_in_multiplier), positions,
                segment_ids,
            )
        x = (
            x
            + _scaled(ssm, cfg.ssm_out_multiplier)
            + _scaled(att, cfg.attention_out_multiplier)
        )
        with jax.named_scope("mlp_dense"):
            x = x + MLP(cfg, name="mlp")(
                RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
            )
        return nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))


class FalconH1(nn.Module):
    """Decoder-only parallel-hybrid LM. Returns logits [B, T, vocab]."""

    cfg: FalconH1Config

    @nn.compact
    def __call__(
        self, tokens, positions=None, segment_ids=None, return_hidden=False
    ):
        return decoder_lm(
            self.cfg, FalconH1Block, tokens, positions, segment_ids, False,
            return_hidden=return_hidden,
        )


FALCON_H1_CONFIGS: dict[str, FalconH1Config] = {
    # Test scale: 5 query heads a K/V head, 4 SSM heads in 2 groups.
    "falcon_h1_tiny": FalconH1Config(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=10,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        max_seq_len=128,
        remat=False,
        ssm_heads=4,
        ssm_head_dim=16,
        ssm_state=32,
        ssm_chunk=16,
    ),
}
