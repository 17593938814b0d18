"""Normalization ops. RMSNorm is the Llama/Mixtral norm, LayerNorm the
Phi-4-flash family's; computed in fp32."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with fp32 accumulation, output cast back to x.dtype.

    XLA fuses this into neighbors on TPU; a Pallas fusion only pays off when
    combined with quantization, so the plain version is the default.
    """
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5
) -> jax.Array:
    """LayerNorm (mean and variance over the last axis, a scale and a
    bias) with fp32 accumulation, output cast back to x.dtype."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (
        y * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    ).astype(dtype)
