"""Gated delta rule in the two forms serving needs, with a decay per
channel (Kimi Delta Attention, arXiv:2510.26692: ``g`` [.., H, d_k]) or
one a head (Gated DeltaNet, arXiv:2412.06464: ``g`` [.., H]). Per head
the state ``S`` is a [d_k, d_v] float32 matrix, d_k and d_v any sizes,
and one token does

    S' = Diag(alpha_t) S_{t-1}            alpha_t = exp(g_t) in (0, 1]
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

- ``kda_step``: exactly that, one token a row (decode).
- ``kda_chunk``: the same recurrence over blocks of ``BLOCK`` positions
  (prefill). Inside a block, with G_t the running sum of g and
  w_t = v_t - S'^T_t k_t the delta each position writes,

      (I + A Diag(beta)) W = V - (K * exp(G)) S_0,
      A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])   (s < t)

  is one unit-lower-triangular system per head, solved once; outputs
  and the outgoing state are then products with S_0 and W, and the state
  is carried block to block by ``lax.scan``. Every exponent is a
  difference G_t - G_s with s <= t, so never positive: no decay, however
  strong, overflows (the factored exp(G_t) * exp(-G_s) form would).
  With one decay a head the sum over c leaves the exponent,
  A = (K K^T) * exp(G_t - G_s): a matmul times a [C, C] matrix, and no
  [C, C, d_k] product is built; the system, its solve, the carry and
  the rule for padding are the same lines either way.

A position under ``valid == False`` is the identity on the state
(alpha = 1, beta = 0): a right-padded tail leaves ``S`` bit-equal to
where the last real token left it, and left padding leaves the zero
state zero. Its own output row is junk and nobody reads it.

``causal_conv`` is the depthwise short convolution in front of q, k and
v, with the last ``K - 1`` inputs of a row kept between calls.

Plain XLA operations; the state arithmetic is float32 at ``highest``
matmul precision whatever the activations are. Forward only.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: Positions per block of the chunkwise form.
BLOCK = 64
_HI = jax.lax.Precision.HIGHEST


def kda_step(q, k, v, g, beta, state):
    """One token a row. q, k [B,H,dk]; g [B,H,dk] or [B,H]; v [B,H,dv];
    beta [B,H]; state [B,H,dk,dv]. Returns (o [B,H,dv] float32, new
    state in the state's dtype)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if g.ndim == beta.ndim:
        g = g[..., None]  # one decay a head, over every channel
    s = state.astype(f32) * jnp.exp(g)[..., None]
    w = v - jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HI)
    s = s + (beta[..., None] * k)[..., None] * w[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
    return o, s.astype(state.dtype)


def _block(carry, xs):
    """One block of ``BLOCK`` positions, all rows and heads at once.
    carry S [B,H,dk,dv]; xs q,k [B,C,H,dk], g [B,C,H,dk] or (one decay
    a head) [B,C,H,1], v [B,C,H,dv], beta [B,C,H]."""
    s0 = carry
    q, k, v, g, beta = xs
    c = q.shape[1]
    # [B,H,C,*]: heads batch, positions rows.
    q, k, v, g = (jnp.moveaxis(a, 2, 1) for a in (q, k, v, g))
    beta = jnp.moveaxis(beta, 2, 1)  # [B,H,C]
    cum = jnp.cumsum(g, axis=2)  # G_t, <= 0 and non-increasing
    # decay[t, s, c] = exp(G_t[c] - G_s[c]) for s <= t (else masked to
    # 0 below): never a positive exponent.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]
    t_idx = jnp.arange(c)
    lower = t_idx[:, None] >= t_idx[None, :]  # s <= t
    decay = jnp.where(lower[..., None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    if g.shape[-1] == 1:
        # One decay a head: it leaves the sum over channels.
        kk = jnp.einsum("bhtk,bhsk->bhts", k, k, precision=_HI) * decay[..., 0]
        qk = jnp.einsum("bhtk,bhsk->bhts", q, k, precision=_HI) * decay[..., 0]
    else:
        kk = jnp.sum(k[:, :, :, None, :] * k[:, :, None, :, :] * decay, axis=-1)
        qk = jnp.sum(q[:, :, :, None, :] * k[:, :, None, :, :] * decay, axis=-1)
    strict = (t_idx[:, None] > t_idx[None, :]).astype(kk.dtype)
    system = jnp.eye(c, dtype=kk.dtype) + kk * strict * beta[:, :, None, :]
    gamma = jnp.exp(cum)  # [B,H,C,dk or 1]
    rhs = v - jnp.einsum("bhtk,bhkv->bhtv", k * gamma, s0, precision=_HI)
    w = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True
    )
    bw = beta[..., None] * w  # [B,H,C,dv]
    o = jnp.einsum("bhtk,bhkv->bhtv", q * gamma, s0, precision=_HI)
    o = o + jnp.einsum("bhts,bhsv->bhtv", qk, bw, precision=_HI)
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # exp(G_C - G_s) <= 1
    s1 = gamma[:, :, -1, :, None] * s0 + jnp.einsum(
        "bhsk,bhsv->bhkv", k * to_end, bw, precision=_HI
    )
    return s1, jnp.moveaxis(o, 1, 2)  # o back to [B,C,H,dv]


def kda_chunk(q, k, v, g, beta, state, valid: Optional[jax.Array] = None):
    """``T`` tokens a row, in blocks of ``BLOCK``. q, k [B,T,H,dk]; g
    [B,T,H,dk] or [B,T,H]; v [B,T,H,dv]; beta [B,T,H]; state
    [B,H,dk,dv]; valid [B,T] bool (None = all). Returns (o [B,T,H,dv]
    float32, new state in the state's dtype). T is padded up to a whole
    number of blocks with positions that are not valid."""
    f32 = jnp.float32
    b, t = q.shape[:2]
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if g.ndim == beta.ndim:
        g = g[..., None]  # one decay a head, over every channel
    if valid is not None:
        g = jnp.where(valid[:, :, None, None], g, 0.0)
        beta = jnp.where(valid[:, :, None], beta, 0.0)
    pad = -t % BLOCK
    if pad:
        # Zero g and beta: the identity, like any other masked position.
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta)
        )
    n = (t + pad) // BLOCK

    def blocks(a):  # [B, n*C, ...] -> [n, B, C, ...]
        return jnp.moveaxis(a.reshape(b, n, BLOCK, *a.shape[2:]), 1, 0)

    s, o = jax.lax.scan(
        _block, state.astype(f32), tuple(blocks(a) for a in (q, k, v, g, beta))
    )
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * BLOCK, *o.shape[3:])
    return o[:, :t], s.astype(state.dtype)


def unit_qk(q, k, eps: float = 1e-6):
    """q and k [.., d_k] float32, each L2-normalised over its head's
    channels, q scaled by d_k ** -0.5: what both delta-rule layers feed
    the rule."""
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + eps
    )
    return unit(q) * q.shape[-1] ** -0.5, unit(k)


def decay_rate(a_log, per_channel: bool):
    """-exp(A_log), float32: what multiplies softplus(its input + bias)
    into g <= 0. [H] for one decay a head, [H, 1] over a head's channels.
    (A function of ``a_log`` alone, so that a caller's own operations
    keep their order around it.)"""
    rate = jnp.exp(a_log)
    return -(rate[:, None] if per_channel else rate)


def causal_conv(x, weight, tail, valid: Optional[jax.Array] = None):
    """Depthwise causal convolution over time, no bias:
    y_t[c] = sum_j weight[j, c] * x_{t-(K-1)+j}[c]. x [B,T,C]; weight
    [K,C]; tail [B,K-1,C], the inputs before this call's first (zeros
    at a row's start). Returns (y [B,T,C], new tail): the last K-1
    inputs up to and including the row's last valid position, so a
    padded tail leaves it where the last real token did. Inputs at
    positions that are not valid count as zeros (left padding is the
    row's empty past)."""
    km1 = weight.shape[0] - 1
    t = x.shape[1]
    if valid is not None:
        x = jnp.where(valid[:, :, None], x, jnp.zeros((), x.dtype))
    buf = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B,K-1+T,C]
    y = sum(
        buf[:, j:j + t] * weight[j].astype(x.dtype) for j in range(km1 + 1)
    )
    if valid is None:
        new_tail = buf[:, t:]
    else:
        # One past the last valid position (0 where the row has none:
        # the old tail stays).
        last = jnp.max(
            jnp.where(valid, jnp.arange(1, t + 1)[None, :], 0), axis=1
        )
        new_tail = jax.vmap(
            lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, km1, 0)
        )(buf, last)
    return y, new_tail.astype(tail.dtype)
