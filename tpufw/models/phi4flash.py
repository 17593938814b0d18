"""Phi-4-mini-flash family (SambaY, arXiv:2507.06607): a SELF-DECODER of
Mamba-1 and sliding-window attention layers whose last two layers fill,
once, what a CROSS-DECODER of Gated Memory Units and cross-attention
layers reads in every layer after. Every block is pre-norm with
LayerNorm (a scale and a bias) and a dense SwiGLU MLP:

    h = x + Mixer(LN(x))
    y = h + MLP(LN(h))

and the mixer is, by layer index i of n (``layer_kinds``):

- even i <= n/2: **Mamba-1** (``MambaMixer``; ``tpufw.ops.mamba``): a
  [N, D] float32 state a row and the convolution's last ``kernel - 1``
  inputs, cache leaves ``mamba_state`` and ``conv_state``, per-slot STATE
  of ``tpufw.ops.kv_store``. Layer n/2 ALSO hands on the MEMORY ``M``:
  its scan's output with the ``D`` skip, before the gate and the output
  projection, of the call's own tokens only. Nothing stores it.
- odd i < n/2: differential attention over a window, a RING of its last
  ``sliding_window`` keys a row (``kv_store.ring_append``).
- i = n/2 + 1: differential attention over the whole row, which writes
  the model's ONE page pair.
- even i > n/2 + 1: a **Gated Memory Unit** over M: ``W_out (M *
  silu(W_in u))``. Holds no cache.
- odd i > n/2 + 1: differential CROSS-attention: queries of its own over
  layer n/2 + 1's keys and values, read through that layer's ``read``
  (kv_store, READERS THAT ARE NOT THE WRITER). Holds no cache.

DIFFERENTIAL ATTENTION (arXiv:2410.05258), no rotary embedding. Heads
pair up adjacently: query pair p = heads (2p, 2p + 1) = (q1, q2), K/V
pair j = (k1, k2), (v1, v2), pair p reads K/V pair p // G:

    a1 = softmax(q1 k1^T) [v1, v2]      a2 = softmax(q2 k2^T) [v1, v2]
    o_p = RMSNorm(a1 - lam a2) (1 - lam0)

It runs here as ordinary grouped-query attention under another view of
the same bytes: a K/V pair is ONE stored head twice as wide, ``K' = [k1,
k2]``, ``V' = [v1, v2]``, and the queries are padded with zeros, ``q1' =
[q1, 0]``, ``q2' = [0, q2]``, so ``q1' . K' = q1 . k1`` exactly and both
softmaxes are H query heads over Hk / 2 stored heads of ``2 hd`` = 128
lanes: the width ``tpufw.ops.paged_attend`` serves and the store's tiles
like. The backends scale by the stored width's ``(2 hd) ** -0.5``, so the
queries carry ``sqrt(2)`` (as Gemma's ``query_pre_attn_scalar`` does it).

The trunk's units are the PAIRS: n / 4 of (Mamba, window) and n / 4 - 1
of (GMU, cross), each scanned or unrolled with the same parameters
(``self_layers`` / ``self_layer_{p}``, ``cross_layers`` /
``cross_layer_{p}``), the two layers between them (``memory``, ``full``)
on their own; M and the full layer's ``read`` are constants of the
second scan. Serving only.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpufw.models.llama import (
    MLP,
    LlamaConfig,
    cached_attention,
    projection,
)
from tpufw.models.solar_open2 import raw_param
from tpufw.ops import kv_store, layer_norm, multi_head_attention, rms_norm
from tpufw.ops.kda import causal_conv
from tpufw.ops.mamba import selective_chunk, selective_step

#: The recurrent state's type. Not a setting: a probe that wants to see
#: what a narrower state costs rebinds this name before it builds.
MAMBA_STATE_DTYPE = jnp.float32


def layer_kinds(n_layers: int) -> tuple:
    """Kind of each of ``n_layers`` layers (module docstring)."""
    half = n_layers // 2
    if n_layers % 4 or n_layers < 8:
        raise ValueError(
            f"n_layers={n_layers}: whole (Mamba, attention) pairs on both "
            "sides of the memory and the full layer, at least one of each"
        )
    return tuple(
        ("mamba" if i % 2 == 0 else "window") if i < half
        else "memory" if i == half
        else "full" if i == half + 1
        else "gmu" if i % 2 == 0
        else "cross"
        for i in range(n_layers)
    )


def lambda_init(depth):
    """Differential attention's ``lam0`` at zero-based layer ``depth`` (a
    Python int, or a traced scalar inside a scanned trunk)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * depth)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig(LlamaConfig):
    """LlamaConfig's fields describe the attention heads AS PUBLISHED (40
    query heads and 20 K/V heads of 64), the dense MLP (``d_ff``) and the
    trunk; the defaults are Phi-4-mini-flash-reasoning's."""

    vocab_size: int = 200_064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    d_ff: int = 10_240
    #: LayerNorm's epsilon, and the sub-layer RMSNorm's.
    rms_eps: float = 1e-5
    max_seq_len: int = 262_144
    tie_embeddings: bool = True
    use_rope: bool = False
    attention_qkv_bias: bool = True
    #: The window of the self-decoder's attention layers, kept as a ring.
    sliding_window: int = 512
    window_ring: bool = True
    # --- the Mamba-1 mixer ---
    mamba_state: int = 16
    mamba_conv: int = 4
    mamba_expand: int = 2

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def mamba_dt_rank(self) -> int:
        return -(-self.d_model // 16)

    @property
    def kv_store_head_dim(self) -> int:
        """Lanes of a stored head: a K/V PAIR, ``[k1, k2]``."""
        return 2 * self.head_dim

    @property
    def kv_pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def kv_store_heads(self) -> int:
        """Stored heads a slot of the page arena holds: the K/V pairs,
        rounded up to whole tiles of 8 sublanes (10 -> 16, six of zeros).
        XLA:TPU's tiling pads a bfloat16 ``[10, 128]`` block to 16 rows in
        HBM anyway; held so, ``[n_pages, page * 16, 128]`` is the leaf's
        own bytes and the decode step reads it in place
        (``kv_store._stored_heads``, ``paged_attend.serves``)."""
        return -(-self.kv_pairs // 8) * 8

    @property
    def kv_page_readers(self) -> int:
        """Layers that read the one page pair in a cached call: the full
        layer that writes it and every cross layer (``kv_store.
        page_readers``)."""
        return 1 + sum(k == "cross" for k in layer_kinds(self.n_layers))

    def n_params(self, include_embed: bool = True) -> int:
        d, inner, n = self.d_model, self.mamba_inner, self.mamba_state
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        diff = 4 * self.head_dim + 2 * self.head_dim
        per = {
            "mamba": (
                d * 2 * inner + (self.mamba_conv + 1) * inner
                + inner * (self.mamba_dt_rank + 2 * n)
                + self.mamba_dt_rank * inner + inner
                + n * inner + inner + inner * d
            ),
            "window": d * (q + 2 * kv) + q + 2 * kv + q * d + d + diff,
            "gmu": 2 * d * inner,
            "cross": d * q + q + q * d + d + diff,
        }
        per["memory"], per["full"] = per["mamba"], per["window"]
        body = 3 * d * self.d_ff + 4 * d
        total = 2 * d + sum(
            per[k] + body for k in layer_kinds(self.n_layers)
        )
        if include_embed:
            total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return total


class LayerNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        def leaf(name, init):
            return self.param(
                name, nn.with_logical_partitioning(init, ("norm",)),
                (x.shape[-1],), jnp.float32,
            )

        return layer_norm(
            x, leaf("scale", nn.initializers.ones_init()),
            leaf("bias", nn.initializers.zeros_init()), self.eps,
        )


class MambaMixer(nn.Module):
    """One Mamba-1 mixer. x [B,T,d] -> (out [B,T,d], y [B,T,inner]
    float32: the scan's output with the skip, before the gate). With
    ``cfg.decode`` the state and the convolution's tail live in the
    "cache" collection and every call continues from them: T > 1 runs
    the scan over the call's tokens (prefill, whole or in chunks), T == 1
    the one-step rule (decode). ``segment_ids == 0`` marks padding and a
    pool's done rows, which leave both exactly as they were."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg = self.cfg
        b, t, _ = x.shape
        inner, n, rank = cfg.mamba_inner, cfg.mamba_state, cfg.mamba_dt_rank
        km1 = cfg.mamba_conv - 1
        f32 = jnp.float32
        valid = None if segment_ids is None else segment_ids > 0

        a, z = jnp.split(
            projection(
                cfg, x, 2 * inner, -1, ("embed",), ("mlp",), "in_proj"
            ),
            2, axis=-1,
        )
        conv_w = raw_param(
            self, cfg, "conv", (km1 + 1, inner),
            nn.initializers.lecun_normal(),
        )
        zeros, ones = nn.initializers.zeros_init(), nn.initializers.ones_init()
        conv_b = raw_param(self, cfg, "conv_bias", (inner,), zeros)
        if cfg.decode:
            tail = kv_store.slot_state(
                self, "conv_state", (b, km1, inner), cfg.dtype
            )
            state = kv_store.slot_state(
                self, "mamba_state", (b, n, inner), MAMBA_STATE_DTYPE
            )
            tail0, s0 = tail.value, state.value
        else:
            tail0 = jnp.zeros((b, km1, inner), cfg.dtype)
            s0 = jnp.zeros((b, n, inner), MAMBA_STATE_DTYPE)
        with jax.named_scope("mamba_conv"):
            a, tail1 = causal_conv(a, conv_w, tail0, valid)
            a = nn.silu(a + conv_b.astype(a.dtype))
        r, b_in, c_in = jnp.split(
            projection(
                cfg, a, rank + 2 * n, -1, ("mlp",), ("lora",), "x_proj"
            ),
            [rank, rank + n], axis=-1,
        )
        dt = jax.nn.softplus(
            projection(
                cfg, r, inner, -1, ("lora",), ("mlp",), "dt_proj",
                use_bias=True,
            ).astype(f32)
        )
        a_neg = -jnp.exp(raw_param(self, cfg, "A_log", (n, inner), zeros).astype(f32))
        d_skip = raw_param(self, cfg, "D", (inner,), ones)

        if cfg.decode and t == 1:
            with jax.named_scope("mamba_step"):
                if valid is not None:
                    # Padding is the identity: decay 1, nothing written.
                    dt = jnp.where(valid[:, :, None], dt, 0.0)
                y, s1 = selective_step(
                    a[:, 0], dt[:, 0], a_neg, b_in[:, 0], c_in[:, 0],
                    d_skip, s0,
                )
                y = y[:, None]
        else:
            with jax.named_scope("mamba_chunk"):
                y, s1 = selective_chunk(
                    a, dt, a_neg, b_in, c_in, d_skip, s0, valid
                )
        if cfg.decode:
            tail.value, state.value = tail1, s1
        out = projection(
            cfg, (y * nn.silu(z.astype(f32))).astype(cfg.dtype), cfg.d_model,
            -1, ("mlp",), ("embed",), "out_proj",
        )
        return out, y


class GatedMemoryUnit(nn.Module):
    """``W_out (M * silu(W_in u))``: the memory [B,T,inner] float32 of
    the call's own tokens, gated by this layer's input. Stores nothing."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, memory):
        cfg = self.cfg
        gate = projection(
            cfg, x, cfg.mamba_inner, -1, ("embed",), ("mlp",), "in_proj"
        )
        y = memory * nn.silu(gate.astype(jnp.float32))
        return projection(
            cfg, y.astype(cfg.dtype), cfg.d_model, -1,
            ("mlp",), ("embed",), "out_proj",
        )


def paired_queries(q):
    """[B,T,H,hd] -> [B,T,H,2 hd]: even heads ``[q, 0]``, odd heads ``[0,
    q]``, with the ``sqrt(2)`` that turns the backends' ``(2 hd) ** -0.5``
    into ``hd ** -0.5`` (module docstring)."""
    b, t, h, hd = q.shape
    q = (q * jnp.asarray(math.sqrt(2.0), q.dtype)).reshape(b, t, h // 2, 2, hd)
    zero = jnp.zeros_like(q[:, :, :, 0])
    return jnp.stack(
        [
            jnp.concatenate([q[:, :, :, 0], zero], axis=-1),
            jnp.concatenate([zero, q[:, :, :, 1]], axis=-1),
        ],
        axis=3,
    ).reshape(b, t, h, 2 * hd)


class DiffAttention(nn.Module):
    """Differential attention of ``kind`` "window", "full" or "cross"
    (module docstring). x [B,T,d] -> (out [B,T,d], reader): ``reader``
    is how a later layer attends this one's cache ("full" with
    ``cfg.decode``; else None); a "cross" layer is handed one as
    ``shared`` (decode) or the full layer's (K', V') (no cache)."""

    cfg: Phi4FlashConfig
    kind: str = "window"

    @nn.compact
    def __call__(self, x, segment_ids, depth, shared=None):
        cfg = self.cfg
        b, t, _ = x.shape
        h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        window = cfg.sliding_window if self.kind == "window" else None

        def heads(name, n):
            return projection(
                cfg, x, (n, hd), -1, ("embed",),
                ("q_heads" if name == "q" else "kv_heads", "head_dim"),
                name, use_bias=cfg.attention_qkv_bias,
            )

        q = paired_queries(heads("q", h))
        reader = None
        if self.kind == "cross":
            if cfg.decode:
                out = shared(q)
            else:
                out = self._uncached(q, *shared, segment_ids, None)
        else:
            # Adjacent heads side by side: a pair is one stored head.
            k = heads("k", hk).reshape(b, t, hk // 2, 2 * hd)
            v = heads("v", hk).reshape(b, t, hk // 2, 2 * hd)
            if cfg.decode:
                out, reader = cached_attention(
                    self, cfg, q, k, v, segment_ids, window
                )
            else:
                out = self._uncached(q, k, v, segment_ids, window)
                reader = (k, v)

        f32 = jnp.float32
        normal = nn.initializers.normal(0.1)
        lq1, lk1, lq2, lk2 = (
            raw_param(self, cfg, name, (hd,), normal).astype(f32)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
        )
        lam0 = lambda_init(depth)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
        out = out.reshape(b, t, h // 2, 2, 2 * hd).astype(f32)
        scale = self.param(
            "subln",
            nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("norm",)
            ),
            (2 * hd,),
            f32,
        )
        out = rms_norm(
            out[:, :, :, 0] - lam * out[:, :, :, 1], scale, cfg.rms_eps
        ) * (1.0 - lam0)
        return projection(
            cfg, out.astype(cfg.dtype), cfg.d_model, (-2, -1),
            ("heads", "head_dim"), ("embed",), "o", use_bias=True,
        ), reader

    @staticmethod
    def _uncached(q, k, v, segment_ids, window):
        return multi_head_attention(
            q, k, v, causal=True, segment_ids=segment_ids,
            sliding_window=window, backend="xla",
        )


class Phi4FlashBlock(nn.Module):
    """One layer of ``kind``. Returns (x, handed): the memory from a
    "memory" layer, the reader from a "full" one, else what it was
    given."""

    cfg: Phi4FlashConfig
    kind: str = "mamba"

    @nn.compact
    def __call__(self, x, segment_ids, depth, handed=None):
        cfg, kind = self.cfg, self.kind
        u = LayerNorm(cfg.rms_eps, name="mixer_norm")(x)
        if kind in ("mamba", "memory"):
            mix, y = MambaMixer(cfg, name="mamba")(u, segment_ids)
            if kind == "memory":
                handed = y
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                mix = GatedMemoryUnit(cfg, name="gmu")(u, handed)
        else:
            scope = {
                "window": "attn_window", "full": "attn_global",
                "cross": "attn_cross",
            }[kind]
            with jax.named_scope(scope):
                mix, reader = DiffAttention(cfg, kind=kind, name="attn")(
                    u, segment_ids, depth, handed
                )
            if kind == "full":
                handed = reader
        x = x + mix
        with jax.named_scope("mlp_dense"):
            x = x + MLP(cfg, name="mlp")(
                LayerNorm(cfg.rms_eps, name="mlp_norm")(x)
            )
        x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))
        return x, handed


class Phi4FlashPair(nn.Module):
    """The trunk's unit: two blocks, ``kinds`` in order, at depths
    ``depth`` and ``depth + 1``. The first is handed ``first``, the
    second ``second``."""

    cfg: Phi4FlashConfig
    kinds: tuple = ("mamba", "window")

    @nn.compact
    def __call__(self, x, segment_ids, depth, first=None, second=None):
        for j, (kind, handed) in enumerate(zip(self.kinds, (first, second))):
            x, _ = Phi4FlashBlock(self.cfg, kind=kind, name=kind)(
                x, segment_ids, depth + j, handed
            )
        return x


class Phi4Flash(nn.Module):
    """Decoder-hybrid-decoder LM. Returns logits [B, T, vocab]."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(
        self, tokens, positions=None, segment_ids=None, return_hidden=False
    ):
        cfg = self.cfg
        kinds = layer_kinds(cfg.n_layers)
        half = cfg.n_layers // 2
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=1.0), ("vocab", "embed")
            ),
            name="embed",
        )
        x = nn.with_logical_constraint(
            embed(tokens), ("batch", "act_seq", "act_embed")
        )

        def pairs(x, name, pair_kinds, depth0, n, *handed):
            """``n`` pairs from depth ``depth0``, scanned or unrolled."""
            if not cfg.scan_layers:
                for p in range(n):
                    x = Phi4FlashPair(
                        cfg, kinds=pair_kinds, name=f"{name}_layer_{p}"
                    )(x, segment_ids, depth0 + 2 * p, *handed)
                return x

            def body(mdl, h, depth):
                return mdl(h, segment_ids, depth, *handed), None

            x, _ = nn.scan(
                body,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True},
                length=n,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(
                Phi4FlashPair(cfg, kinds=pair_kinds, name=f"{name}_layers"),
                x, depth0 + 2 * jnp.arange(n),
            )
            return x

        x = pairs(x, "self", kinds[:2], 0, half // 2)
        x, memory = Phi4FlashBlock(cfg, kind="memory", name="memory")(
            x, segment_ids, half
        )
        x, reader = Phi4FlashBlock(cfg, kind="full", name="full")(
            x, segment_ids, half + 1
        )
        x = pairs(
            x, "cross", kinds[half + 2:half + 4], half + 2, half // 2 - 1,
            memory, reader,
        )
        x = LayerNorm(cfg.rms_eps, name="final_norm")(x)
        if return_hidden:
            return x
        # The tied head, accumulated and handed back in float32.
        logits = jax.lax.dot_general(
            x, embed.embedding.astype(x.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return nn.with_logical_constraint(
            logits, ("batch", "act_seq", "act_vocab")
        )


PHI4FLASH_CONFIGS: dict[str, Phi4FlashConfig] = {
    # Test scale: the least depth with every kind of layer, 2 K/V pairs
    # under 4 query pairs, a window shorter than the row.
    "phi4flash_tiny": Phi4FlashConfig(
        vocab_size=256,
        d_model=64,
        n_layers=8,
        n_heads=8,
        n_kv_heads=4,
        head_dim=8,
        d_ff=128,
        max_seq_len=128,
        sliding_window=16,
        remat=False,
        mamba_state=4,
    ),
}
