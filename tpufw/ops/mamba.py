"""Mamba-1's selective scan (arXiv:2312.00752) in the two forms serving
needs. Per channel d the state is a row of N float32 numbers and one
token does

    S_t[n, d] = exp(dt_t[d] A[n, d]) S_{t-1}[n, d] + dt_t[d] x_t[d] B_t[n]
    y_t[d]    = sum_n S_t[n, d] C_t[n] + D[d] x_t[d]

with a decay per channel AND per state (``A`` [N, D] < 0: ``ops/ssd.py``
has one number a head and contracts blocks with matmuls; here no two
channels share a decay, so there is no matmul to block into) and B and C
shared by every channel. The state is kept ``[B, N, D]``, channels on
the lanes: N = 16 on the lanes would pad every row to 128 of them, eight
times the bytes in HBM.

- ``selective_step``: exactly that, one token a row (decode).
- ``selective_chunk``: the same recurrence over T tokens from a carried
  state, token by token in a ``lax.scan`` (prefill, whole or in chunks):
  the work a token is one multiply-add over ``[N, D]``. Measured on the
  chip at D = 5120, N = 16 (PERF.md section 6, PR 45): 0.6 ms for 512
  tokens, 1.2 us a token, the same at every unroll from 1 to 64, so the
  loop is left as it is.

A position with ``dt == 0`` is the identity on the state (decay 1,
nothing written), which is how ``valid == False`` is spelled: a padded
tail leaves ``S`` bit-equal to where the last real token left it. Its
own output row is junk and nobody reads it.

Plain XLA operations, float32 whatever the activations are. Forward only.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _token(state, x, dt, a_neg, b_in, c_in):
    """One token of every row. state [B,N,D]; x, dt [B,D]; a_neg [N,D];
    b_in, c_in [B,N]. Returns (new state, y [B,D] without the skip)."""
    decay = jnp.exp(dt[:, None, :] * a_neg[None])
    state = decay * state + b_in[:, :, None] * (dt * x)[:, None, :]
    return state, jnp.sum(state * c_in[:, :, None], axis=1)


def selective_step(x, dt, a_neg, b_in, c_in, d_skip, state):
    """One token a row. x [B,D]; dt [B,D] (after its softplus; 0 where
    the row is not live); a_neg [N,D] < 0 (``-exp(A_log)``); b_in, c_in
    [B,N]; d_skip [D]; state [B,N,D]. Returns (y [B,D] float32, new
    state in the state's dtype)."""
    f32 = jnp.float32
    x, dt, b_in, c_in = (a.astype(f32) for a in (x, dt, b_in, c_in))
    s, y = _token(state.astype(f32), x, dt, a_neg.astype(f32), b_in, c_in)
    return y + d_skip.astype(f32) * x, s.astype(state.dtype)


def selective_chunk(
    x, dt, a_neg, b_in, c_in, d_skip, state,
    valid: Optional[jax.Array] = None,
):
    """``T`` tokens a row. x, dt [B,T,D] (dt after its softplus); a_neg
    [N,D]; b_in, c_in [B,T,N]; d_skip [D]; state [B,N,D]; valid [B,T]
    bool (None = all). Returns (y [B,T,D] float32, new state in the
    state's dtype)."""
    f32 = jnp.float32
    x, dt, b_in, c_in = (a.astype(f32) for a in (x, dt, b_in, c_in))
    if valid is not None:
        dt = jnp.where(valid[:, :, None], dt, 0.0)
    a_neg = a_neg.astype(f32)

    def body(s, xs):
        return _token(s, *xs[:2], a_neg, *xs[2:])

    s, y = jax.lax.scan(
        body, state.astype(f32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, b_in, c_in)),
    )
    y = jnp.moveaxis(y, 0, 1) + d_skip.astype(f32) * x
    return y, s.astype(state.dtype)
