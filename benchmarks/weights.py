"""Weights from the seed, made on the device in one jitted call, in the
type they are served in (bfloat16; norm scales float32 ones). Named and
shaped by the family's plain reference, so the reference needs nothing the
program has made; the family's adapter re-labels them for the program."""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2**31 - 1)), seed // (2**31 - 1))


def make_weights(specs: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """``specs``: name -> (shape, fan_in). fan_in > 0: normal scaled by
    fan_in ** -0.5, so every branch carries weight beside the residual;
    0: a norm scale, ones in float32; -1: the embedding, unit normal."""
    names = sorted(specs)

    def build(key):
        out = {}
        for name in names:
            shape, fan_in = specs[name]
            if fan_in == 0:
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            # crc32 of the name, not its rank: adding a layer leaves the
            # other leaves' numbers as they were.
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            std = 1.0 if fan_in < 0 else float(fan_in) ** -0.5
            out[name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))
