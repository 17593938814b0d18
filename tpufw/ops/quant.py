"""Weight-only int8 quantization for serving.

Autoregressive decode is HBM-bandwidth-bound: every step streams every
weight once to produce one token per sequence. Storing projection
weights as int8 + a per-output-channel fp scale halves the bytes moved
(vs bf16), which is the first-order decode-throughput lever on TPU; the
matmul itself still runs in the activation dtype (the int8->bf16 cast
and the scale multiply fuse into the surrounding ops under XLA).

Scope: the projection kernels per block (attention q/k/v/o, MLA's
q_a/q_b/kv_a, MLP gate/up/down), the dedicated LM head, and the raw
expert stacks of Mixtral (``moe`` scope) and DeepSeek (``routed``
scope; stacks of a chip's held experts alike) — routers and MLA's small kv_b latent up-projection stay fp. Embeddings stay full precision (a gather, and for tied
heads the two uses want incompatible scale granularities).
Per-OUTPUT-channel symmetric scales keep the quantization error
independent per output unit, and scaling AFTER the contraction is
algebraically exact for that granularity.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

#: projection module name -> number of CONTRACTED (input) dims of its
#: kernel; remaining trailing dims are output channels. Extra LEADING
#: dims (nn.scan layer stacks, Gemma pair stacks) are batch dims.
_PROJ_IN_DIMS = {
    "q": 1, "k": 1, "v": 1, "o": 2,
    # MLA (deepseek): compressed-q pair and the packed KV-latent
    # down-projection; the latent up-projection (kv_b_kernel, a raw
    # array) stays fp — it is tiny and the absorbed decode contracts
    # its halves separately.
    "q_a": 1, "q_b": 1, "kv_a": 1,
    "gate": 1, "up": 1, "down": 1,
    # Linear attention (tpufw.models.solar_open2 KDALayer): the two
    # low-rank pairs (decay, output gate) and the write strength; its
    # q/k/v/o are shaped like softmax attention's. The softmax layer's
    # output gate is a flat [d, H*hd] "gate".
    "f_a": 1, "f_b": 1, "g_a": 1, "g_b": 1, "beta": 1,
    # A state-space mixer's two projections (tpufw.models.falcon_h1
    # SSMMixer), flat [d, z + xBC + dt] and [inner, d].
    "in_proj": 1, "out_proj": 1,
    # Gated DeltaNet (tpufw.models.olmo_hybrid): the decay's [d, H]
    # projection; its q/k/v/o, ``gate`` and ``beta`` are shaped like the
    # names above.
    "decay": 1,
    # A Mamba-1 mixer's two small projections (tpufw.models.phi4flash
    # MambaMixer): [inner, dt_rank + 2 N] and [dt_rank, inner] with the
    # time step's bias; its ``in_proj`` / ``out_proj`` and the Gated
    # Memory Unit's are shaped like the names above.
    "x_proj": 1, "dt_proj": 1,
    # The dedicated LM head ([D, V]) is the largest single matmul a
    # decode step streams; tied (Gemma) embeddings stay fp — the gather
    # and the attend contraction want incompatible scale granularities.
    "lm_head": 1,
}
#: unstacked kernel rank per module (leading dims beyond this = stacks).
_PROJ_RANK = {
    "q": 3, "k": 3, "v": 3, "o": 3,
    "q_a": 2, "q_b": 3, "kv_a": 2,
    "gate": 2, "up": 2, "down": 2,
    "f_a": 2, "f_b": 2, "g_a": 2, "g_b": 2, "beta": 2,
    "in_proj": 2, "out_proj": 2,
    "decay": 2,
    "x_proj": 2, "dt_proj": 2,
    "lm_head": 2,
}
#: Mixtral expert stacks: RAW [E, in, out] arrays (not {kernel} modules)
#: named w_* inside the moe scope; input dim is always axis -2, scale is
#: per (expert, out-channel). The router stays fp (tiny).
_EXPERT_KEYS = {"w_gate", "w_up", "w_down"}


def quantize_kernel(w: jax.Array, in_axes: tuple) -> dict:
    """[*stack, *in, *out] fp kernel -> {"q_kernel" int8, "scale" fp32}
    with per-output-channel symmetric scales (reduced over ``in_axes``;
    scale shape = the remaining dims)."""
    amax = jnp.max(jnp.abs(w), axis=in_axes, keepdims=False)
    scale = (amax / 127.0 + 1e-12).astype(jnp.float32)
    # Broadcast scale back across the reduced axes for the division.
    bshape = list(w.shape)
    for ax in in_axes:
        bshape[ax] = 1
    q = jnp.clip(
        jnp.round(w / scale.reshape(bshape)), -127, 127
    ).astype(jnp.int8)
    return {"q_kernel": q, "scale": scale}


def quantize_params(params: Any) -> Any:
    """Walk a decoder param tree and replace every projection kernel
    with its int8 form ({"q_kernel", "scale"} in place of {"kernel"}).
    Handles plain, nn.scan-stacked, and Gemma pair-stacked layouts.
    Raises if the tree carries LoRA adapters (merge first)."""
    from flax.linen import meta

    from tpufw.models.lora import has_lora

    # Trees straight out of ``model.init`` carry flax AxisMetadata boxes
    # (LogicallyPartitioned) around each leaf; unbox (identity on raw
    # trees) so the walk below sees arrays. The quantized tree is raw —
    # the quant modules re-declare their own logical partitioning.
    params = meta.unbox(params)

    if has_lora(params):
        raise ValueError(
            "quantize_params on a LoRA tree: run merge_lora first "
            "(adapters must fold into the kernels they modify)"
        )
    hit = []

    def walk(node, parent=""):
        if not isinstance(node, dict):
            return node
        out = {}
        for key, val in node.items():
            if (
                key in _PROJ_IN_DIMS
                and isinstance(val, dict)
                and "kernel" in val
                and set(val) <= {"kernel", "bias"}
            ):
                w = val["kernel"]
                n_in = _PROJ_IN_DIMS[key]
                n_stack = w.ndim - _PROJ_RANK[key]
                in_axes = tuple(range(n_stack, n_stack + n_in))
                out[key] = quantize_kernel(w, in_axes)
                if "bias" in val:
                    # Qwen qkv bias: tiny, stays fp (the kernel carries
                    # the bandwidth; QuantDenseGeneral adds it back).
                    out[key]["bias"] = val["bias"]
                hit.append(key)
            elif (
                key in _EXPERT_KEYS
                and parent in ("moe", "routed")
                and not isinstance(val, dict)
                and getattr(val, "ndim", 0) >= 3
            ):
                # [*stack, E, in, out] expert stack (nn.scan adds a
                # leading layer dim) -> int8 + per-(…, E, out) scales
                # (tpufw.models.mixtral.QuantExpertKernel's shapes).
                # Gated on the 'moe' parent scope: the functional
                # pipeline params carry same-named DENSE stacks that
                # must stay untouched.
                out[key] = quantize_kernel(val, (val.ndim - 2,))
                hit.append(key)
            else:
                out[key] = walk(val, parent=key)
        return out

    quantized = walk(params)
    if not hit:
        raise ValueError(
            "quantize_params: no projection kernels found (expected "
            f"modules named {sorted(_PROJ_IN_DIMS)})"
        )
    return quantized


def quantize_kv(kv: jax.Array, n_feat: int = 1) -> tuple:
    """Per-token symmetric int8 quantization for KV-cache appends.

    ``kv`` is [..., *feat]: the trailing ``n_feat`` dims are the feature
    block quantized together (llama K/V: (heads, head_dim) -> n_feat=2;
    MLA latents: (rank,) -> n_feat=1); every leading dim keeps its own
    scale. Returns (q int8, scale fp32) with ``scale`` shaped like the
    leading dims — the paged pool stores scales page-structured
    ([n_pages, page_size]), one scale per token slot per page, so
    appends are pure scatters (no running-amax requantization of
    already-resident tokens)."""
    axes = tuple(range(kv.ndim - n_feat, kv.ndim))
    amax = jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=axes)
    scale = (amax / 127.0 + 1e-12).astype(jnp.float32)
    bshape = scale.shape + (1,) * n_feat
    q = jnp.clip(
        jnp.round(kv.astype(jnp.float32) / scale.reshape(bshape)),
        -127, 127,
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Inverse of ``quantize_kv``: int8 codes x broadcast fp32 scales,
    accumulated in fp32 and cast to the activation ``dtype`` at the end
    (the cast and multiply fuse into the attention reads under XLA —
    HBM only ever streams the int8 bytes plus one fp32 per token)."""
    n_feat = q.ndim - scale.ndim
    bshape = scale.shape + (1,) * n_feat
    return (q.astype(jnp.float32) * scale.reshape(bshape)).astype(dtype)


def quant_contract(
    x: jax.Array, q_kernel: jax.Array, scale: jax.Array, n_in: int
) -> jax.Array:
    """x ⋅ dequant(kernel): contract x's trailing ``n_in`` dims with the
    kernel's input dims, then apply the per-output-channel scale. The
    int8->activation-dtype cast happens here, fused by XLA — HBM only
    ever streams the int8 bytes."""
    w = q_kernel.astype(x.dtype)
    y = jnp.tensordot(
        x, w,
        axes=(tuple(range(x.ndim - n_in, x.ndim)), tuple(range(n_in))),
    )
    return y * scale.astype(x.dtype)
