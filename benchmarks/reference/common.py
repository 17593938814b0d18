"""Pieces both plain references share. Straightforward ``jax.numpy`` in
float32 at ``highest`` matmul precision; nothing here imports the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: Queries per attention block: bounds the [heads, block, T] score tensor
#: so a reference pass over 8k positions fits beside the weights.
QUERY_BLOCK = 512


#: What a matmul's left operand goes through first: nothing. The int8
#: control swaps in ``int8_rows`` while it traces its own program.
ACT = [lambda x: x]


def mm(x, w):
    """x @ w in float32, the one way the references multiply by a weight."""
    return ACT[0](x) @ up(w)


def int8_rows(x):
    """Dynamic int8 of a matmul input: one absmax scale per row."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def up(w):
    """A stored (bfloat16) weight as float32: the reference's arithmetic
    is float32 on exactly the numbers the program was given."""
    return w.astype(F32)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * up(scale)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, gate, up_w, down):
    return mm(silu(mm(x, gate)) * mm(x, up_w), down)


def causal_attention(q, k, v, scale):
    """q [T,H,dq], k [T,Hk,dq], v [T,Hk,dv] -> [T,H,dv]; full causal
    softmax attention, no cache, computed in blocks of queries. Hk may be
    smaller than H (grouped queries: head h reads kv head h // (H/Hk))."""
    t, h, _ = q.shape
    hk = k.shape[1]
    rep = h // hk
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    qb = min(QUERY_BLOCK, t)
    if t % qb:
        raise ValueError(f"sequence length {t} is not a multiple of {qb}")
    key_pos = jnp.arange(t)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        q_pos = i * qb + jnp.arange(qb)
        s = jnp.where(key_pos[None, None, :] <= q_pos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(t // qb))
    return out.reshape(t, h, v.shape[-1])


def routed_experts(x, gates, w_gate, w_up, w_down):
    """Sum over experts of gate_e(token) * expert_e(token): every expert
    runs over every token and the gate (zero for tokens not routed to it)
    weighs the result. One expert is up-cast at a time."""
    n_experts = w_gate.shape[0]

    def body(e, acc):
        pick = lambda w: jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
        y = swiglu(x, pick(w_gate), pick(w_up), pick(w_down))
        g = jax.lax.dynamic_index_in_dim(gates, e, 1, keepdims=False)
        return acc + g[:, None] * y

    return jax.lax.fori_loop(0, n_experts, body, jnp.zeros_like(x))


def routing_margin(probs, k):
    """How firmly each token chose its experts: the probability of its
    last chosen expert minus that of the best expert it left out. Top-k
    routing is a step function, so where this is within rounding of zero
    two sound computations may route differently and then differ by an
    expert's whole output."""
    vals, _ = jax.lax.top_k(probs, k + 1)
    return vals[:, k - 1] - vals[:, k]


def topk_gates(probs, k, renormalise):
    """Dense [T,E] gate matrix holding the top-k probabilities of each row
    (renormalised to sum to one where the architecture says so)."""
    vals, idx = jax.lax.top_k(probs, k)
    if renormalise:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=F32) * vals[..., None], axis=1)


def int8_round_trip(w, in_axis):
    """What an int8 matmul would multiply by: symmetric int8 with one scale
    per output channel (absmax over the input axis), back in the stored
    type. The control of ``correct`` runs the reference on these, with
    ``int8_rows`` on every matmul's input."""
    w32 = w.astype(F32)
    scale = jnp.max(jnp.abs(w32), axis=in_axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127)
    return (q * scale).astype(w.dtype)


def logits(layer, w, cfg: dict, tokens, at):
    """The decoder trunk both families share: embed, ``layer`` for each
    layer (returning the new hidden state and its routing margin), final
    norm, head. Returns next-token logits [len(at), V] after the positions
    ``at``, and how firmly each of those positions was routed (the least
    ``routing_margin`` over the layers)."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0])
        x = up(w["embed"])[tokens]
        margin = jnp.full(tokens.shape, jnp.inf)
        for i in range(cfg["num_hidden_layers"]):
            x, m = layer(w, i, cfg, x, positions)
            margin = jnp.minimum(margin, m)
        h = rms_norm(x, w["final_norm"], cfg["rms_norm_eps"])
        return mm(h[at], w["lm_head"]), margin[at]
