"""Mamba-2's state-space recurrence (SSD, arXiv:2405.21060) in the two
forms serving needs. Per head the state ``S`` is a [P, N] float32 matrix
(P the head's channels, N the state size) and one token does

    S_t = a_t S_{t-1} + dt_t x_t (outer) B_t     a_t = exp(-dt_t A) in (0, 1]
    y_t = S_t C_t + D x_t

with a SCALAR decay a head (``ops/kda.py`` decays by the channel and
solves a triangular system; here there is neither), B and C shared by
the ``H / G`` heads of a group, and ``D`` a skip.

- ``ssd_step``: exactly that, one token a row (decode).
- ``ssd_chunk``: the same recurrence over blocks of ``block`` positions
  (prefill). Inside a block, with L_t the running sum of log a,

      y_t = exp(L_t) S_0 C_t
            + sum_{s<=t} exp(L_t - L_s) dt_s (C_t . B_s) x_s + D x_t
      S_end = exp(L_end) S_0 + sum_s exp(L_end - L_s) dt_s x_s (outer) B_s

  and the state is carried block to block by ``lax.scan``. Every
  exponent is a difference L_t - L_s with s <= t, so never positive: no
  decay, however strong, overflows.

A position with ``dt == 0`` is the identity on the state (a = 1, nothing
written), which is how ``valid == False`` is spelled: a right-padded tail
leaves ``S`` bit-equal to where the last real token left it, and left
padding leaves the zero state zero. Its own output row is junk and nobody
reads it.

Plain XLA operations; the state arithmetic is float32 at ``highest``
matmul precision whatever the activations are. Forward only.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _grouped(a, groups: int):
    """[B, H, *rest] -> [B, G, H/G, *rest]: head h is of group
    h // (H/G), whose B and C it reads."""
    b, h = a.shape[:2]
    return a.reshape((b, groups, h // groups) + a.shape[2:])


def ssd_step(x, dt, a_rate, b_in, c_in, d_skip, state):
    """One token a row. x [B,H,P]; dt [B,H] (after its softplus; 0 where
    the row is not live); a_rate [H] > 0 (``exp(A_log)``); b_in, c_in
    [B,G,N]; d_skip [H]; state [B,H,P,N]. Returns (y [B,H,P] float32,
    new state in the state's dtype)."""
    f32 = jnp.float32
    x, dt, b_in, c_in = (a.astype(f32) for a in (x, dt, b_in, c_in))
    g = b_in.shape[1]
    decay = jnp.exp(-dt * a_rate.astype(f32))  # [B,H]
    xg = _grouped(x * dt[..., None], g)  # [B,G,Hg,P]
    s = _grouped(state.astype(f32) * decay[..., None, None], g)
    s = s + xg[..., None] * b_in[:, :, None, None, :]
    y = jnp.einsum("bghpn,bgn->bghp", s, c_in, precision=_HI)
    s = s.reshape(state.shape)
    y = y.reshape(x.shape) + d_skip.astype(f32)[:, None] * x
    return y, s.astype(state.dtype)


def _block(carry, xs, *, a_rate, d_skip, groups):
    """One block of C positions, all rows and heads at once. carry S
    [B,H,P,N]; xs x [B,C,H,P], dt [B,C,H], b_in, c_in [B,C,G,N]."""
    s0 = carry
    x, dt, b_in, c_in = xs
    bsz, c, h, p = x.shape
    g = groups
    log_a = -dt * a_rate  # [B,C,H], <= 0
    cum = jnp.cumsum(log_a, axis=1)  # L_t, non-increasing
    cum_h = jnp.moveaxis(cum, 1, 2)  # [B,H,C]
    t_idx = jnp.arange(c)
    lower = t_idx[:, None] >= t_idx[None, :]  # s <= t
    # decay[t, s] = exp(L_t - L_s) for s <= t (else 0): never a positive
    # exponent.
    diff = cum_h[:, :, :, None] - cum_h[:, :, None, :]
    decay = jnp.where(lower, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    cb = jnp.einsum("btgn,bsgn->bgts", c_in, b_in, precision=_HI)
    dt_h = jnp.moveaxis(dt, 1, 2)  # [B,H,C]
    m = (
        _grouped(decay * dt_h[:, :, None, :], g) * cb[:, :, None]
    ).reshape(bsz, h, c, c)
    y = jnp.einsum("bhts,bshp->bthp", m, x, precision=_HI)
    # What the carried state gives each position.
    s0g = _grouped(s0, g)
    from_s0 = jnp.einsum(
        "bghpn,btgn->btghp", s0g, c_in, precision=_HI
    ).reshape(bsz, c, h, p)
    y = y + from_s0 * jnp.exp(cum)[..., None] + d_skip[:, None] * x
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dt  # exp(L_end - L_s) dt_s
    xw = _grouped(jnp.moveaxis(x * to_end[..., None], 1, 2), g)
    wrote = jnp.einsum("bghsp,bsgn->bghpn", xw, b_in, precision=_HI)
    s1 = jnp.exp(cum_h[:, :, -1])[:, :, None, None] * s0 + wrote.reshape(
        s0.shape
    )
    return s1, y


def ssd_chunk(
    x, dt, a_rate, b_in, c_in, d_skip, state,
    valid: Optional[jax.Array] = None, block: int = 128,
):
    """``T`` tokens a row, in blocks of ``block``. x [B,T,H,P]; dt
    [B,T,H] (after its softplus); a_rate, d_skip [H]; b_in, c_in
    [B,T,G,N]; state [B,H,P,N]; valid [B,T] bool (None = all). Returns
    (y [B,T,H,P] float32, new state in the state's dtype). T is padded up
    to a whole number of blocks with positions that are not valid."""
    f32 = jnp.float32
    b, t = x.shape[:2]
    x, dt, b_in, c_in = (a.astype(f32) for a in (x, dt, b_in, c_in))
    if valid is not None:
        dt = jnp.where(valid[:, :, None], dt, 0.0)
    pad = -t % block
    if pad:
        # dt = 0: the identity, like any other masked position.
        x, dt, b_in, c_in = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (x, dt, b_in, c_in)
        )
    n = (t + pad) // block

    def blocks(a):  # [B, n*C, ...] -> [n, B, C, ...]
        return jnp.moveaxis(a.reshape(b, n, block, *a.shape[2:]), 1, 0)

    body = functools.partial(
        _block, a_rate=a_rate.astype(f32), d_skip=d_skip.astype(f32),
        groups=b_in.shape[2],
    )
    s, y = jax.lax.scan(
        body, state.astype(f32), tuple(blocks(a) for a in (x, dt, b_in, c_in))
    )
    y = jnp.moveaxis(y, 0, 1).reshape(b, n * block, *y.shape[3:])
    return y[:, :t], s.astype(state.dtype)
