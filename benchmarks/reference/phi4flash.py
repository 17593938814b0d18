"""Plain forward pass of the Phi-4-mini-flash family, from its published
``config.json`` (huggingface.co/microsoft/Phi-4-mini-flash-reasoning) and,
for what the config cannot carry, "Decoder-Hybrid-Decoder Architecture for
Efficient Reasoning with Long Generation" (Ren et al., 2025: SambaY, the
Gated Memory Unit, differential attention) as the repository's
``modeling_phi4flash.py`` spells it. Dense; ``x`` is [T, hidden]:

    per layer:  h = x + Mixer(LN1(x));  x = h + W2 (silu(g) * v),  [g, v] = LN2(h) W1
    logits = LN(x) E^T                  (the head is the embedding, tied)

``LN`` is LayerNorm with a scale and a bias. The mixer by zero-based layer
index i of n (``layer_kinds``): even i <= n/2 Mamba-1 (layer n/2 also hands
on the memory M); odd i < n/2 differential attention over a window of
``sliding_window`` keys; i = n/2 + 1 differential attention over the whole
row; even i > n/2 + 1 a Gated Memory Unit over M; odd i > n/2 + 1
differential CROSS-attention, its own queries over layer n/2 + 1's keys and
values.

**Mamba-1** (u = LN1(x)), TOKEN BY TOKEN from S = 0:
  [a, z] = u W_in;  a = silu(conv(a) + b_conv)      depthwise causal, kernel d_conv
  [r, B, C] = a W_x                                 dt_rank, d_state, d_state
  dt = softplus(r W_dt + b_dt);  A = -exp(A_log)    A [d_inner, d_state]
  S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * a_t) (x) B_t;  y_t = S_t C_t + D * a_t
  out = (y * silu(z)) W_out;  M = y  (layer n/2: before the gate and W_out)
**GMU**: out = (M * silu(u W_in)) W_out.
**Differential attention**, no rotary embedding, scores scaled by
head_dim ** -0.5, in the PAIRED form: [q, k, v] = u W_qkv + b; query pair p
= heads (2p, 2p + 1) = (q1, q2); K/V pair j = (k1, k2), (v1, v2); pair p
reads K/V pair p // (pairs of queries a pair of keys);
  a1 = softmax(q1 k1^T) [v1, v2];  a2 = softmax(q2 k2^T) [v1, v2]
  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,   lam0 = 0.8 - 0.6 exp(-0.3 i)
  o_p = RMSNorm(a1 - lam a2; subln [2 head_dim]) * (1 - lam0);  out = o W_o + b_o.
A cross layer has q = u W_q + b_q alone and takes k, v from layer n/2 + 1:
the keys up to and including the query's own position.

What the config does not carry is this file's (the configuration file's
``assumed`` says the same, each with its source): the Mamba sizes
(``D_STATE``, ``D_CONV``, ``EXPAND``, dt_rank = ceil(hidden / 16)), which
projections have a bias, ``A_log = log(1..d_state)`` a channel, ``D = 1``,
``b_dt`` the inverse softplus of a log-uniform [1e-3, 1e-1] time step from
a seeded draw, the four ``lam`` vectors normal 0.1.

No experts, so no routing margin: every position reads ``inf``. ``logits``
answers for at most ``MAX_AT`` positions; the MLP runs over ``ROW_BLOCK``
rows at a time, attention over ``common.QUERY_BLOCK`` queries and the head
over ``HEAD_BLOCKS`` slices of the vocabulary, so that a 16,384-token
sequence fits beside the weights.

Weights are named and shaped by this file ([in, out] matrices); it imports
nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# The sizes the config does not carry and the kind of each layer: plain
# arithmetic, one copy for the reference and the cost functions.
from benchmarks.costs.phi4flash import D_CONV, D_STATE, EXPAND, mamba_dims  # noqa: F401
from benchmarks.costs.phi4flash import layer_kinds as _kinds
from benchmarks.reference import common as c

FAMILY = "phi4flash"
#: ``logits`` answers for at most this many leading entries of ``at``: the
#: longest answer the family's cell asks for.
MAX_AT = 2048
#: Rows of the sequence an MLP block takes: [2048, 2 x 10240] float32 is
#: 168 MB at a time, where 16,384 rows at once would be 1.3 GB.
ROW_BLOCK = 2048
#: Slices of the vocabulary the head is computed in (200,064 = 16 x
#: 12,504): the float32 copy of a slice of the tied embedding is 128 MB.
HEAD_BLOCKS = 16
# D_STATE, D_CONV, EXPAND (16, 4, 2) and dt_rank "auto": the Mamba-1 mixer's
# sizes, the family's own (``modeling_phi4flash.py``'s defaults).
#: The time step a channel starts from: log-uniform over this range.
DT_RANGE = (1e-3, 1e-1)
INT8_KEEP = ("embed", "norm", "bias", "conv", "dt_draw", "lambda", "subln", ".D")


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_kinds(cfg: dict):
    n = cfg["num_hidden_layers"]
    if cfg["mb_per_layer"] != 2 or n % 4 or n < 8:
        raise ValueError("this reference covers mb_per_layer 2 and whole pairs on both sides of layers n/2, n/2 + 1")
    return _kinds(cfg)


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def dt_bias(dt_draw):
    """b_dt from the seeded unit-normal draw: with u = Phi(draw) uniform,
    dt = 1e-3 * 100 ** u and b_dt its inverse softplus."""
    u = jax.scipy.stats.norm.cdf(c.up(dt_draw))
    dt = DT_RANGE[0] * (DT_RANGE[1] / DT_RANGE[0]) ** u
    return dt + jnp.log(-jnp.expm1(-dt))


def a_log(cfg: dict):
    """[d_inner, d_state]: log(1..d_state) in every channel."""
    inner, n, _, _ = mamba_dims(cfg)
    return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=c.F32)), (inner, n))


def weight_specs(cfg: dict) -> dict:
    """name -> (shape, fan_in). Biases are drawn small and not zero (fan_in
    the hidden size), so that a bias left out shows."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    inner, n, kk, rank = mamba_dims(cfg)
    if cfg["mlp_bias"] or cfg["lm_head_bias"] or not cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu":
        raise ValueError("this reference covers the family's tied, SiLU configs with no bias in the MLP or the head")
    # The tied head reads the embedding: drawn at hidden ** -0.5 so that
    # the logits have unit variance, as an untied head's would.
    specs = {"embed": ((v, d), d), "final_norm.scale": ((d,), 0), "final_norm.bias": ((d,), d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"layers.{i}."
        specs.update({
            p + "norm1.scale": ((d,), 0), p + "norm1.bias": ((d,), d),
            p + "norm2.scale": ((d,), 0), p + "norm2.bias": ((d,), d),
            p + "mlp.w1": ((d, 2 * f), d),
            p + "mlp.w2": ((f, d), f),
        })
        if kind in ("mamba", "memory"):
            q = p + "mamba."
            specs.update({
                q + "in_proj": ((d, 2 * inner), d),
                q + "conv": ((kk, inner), kk),
                q + "conv_bias": ((inner,), d),
                q + "x_proj": ((inner, rank + 2 * n), inner),
                q + "dt_proj": ((rank, inner), rank),
                # A "unit normal" leaf, as the embedding's draw used to
                # be: what ``dt_bias`` maps onto b_dt.
                q + "dt_draw": ((inner,), -1),
                q + "D": ((inner,), 0),
                q + "out_proj": ((inner, d), inner),
            })
        elif kind == "gmu":
            specs.update({
                p + "gmu.in_proj": ((d, inner), d),
                p + "gmu.out_proj": ((inner, d), inner),
            })
        else:
            q = p + "attn."
            width = h * hd if kind == "cross" else (h + 2 * hk) * hd
            specs.update({
                q + "qkv": ((d, width), d),
                q + "qkv_bias": ((width,), d),
                q + "o": ((h * hd, d), h * hd),
                q + "o_bias": ((d,), d),
                q + "subln": ((2 * hd,), 0),
                **{q + f"lambda_{a}": ((hd,), 100) for a in ("q1", "k1", "q2", "k2")},
            })
    return specs


def layer_norm(x, w, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * c.up(w[p + ".scale"]) + c.up(w[p + ".bias"])


def attention(q, k, v, scale, window=None):
    """q [T,H,dq], k [T,Hk,dq], v [T,Hk,dv] -> [T,H,dv]: causal softmax
    attention over the whole row, or over the last ``window`` keys, in
    blocks of queries; head h reads kv head h // (H / Hk)."""
    t, h, _ = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    qb = min(c.QUERY_BLOCK, t)
    if t % qb:
        raise ValueError(f"sequence length {t} is not a multiple of {qb}")
    key_pos = jnp.arange(t)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        q_pos = i * qb + jnp.arange(qb)
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen = seen & (q_pos[:, None] - key_pos[None, :] < window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    return jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h, v.shape[-1])


def diff_attention(w, p, cfg, i: int, u, kv=None, window=None):
    """Differential attention of layer ``i`` in the paired form. ``kv``:
    another layer's (k, v) (a cross layer). Returns (out, (k, v))."""
    t = u.shape[0]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    qkv = c.mm(u, w[p + "qkv"]) + c.up(w[p + "qkv_bias"])
    q = qkv[:, :h * hd].reshape(t, h, hd)
    if kv is None:
        k = qkv[:, h * hd:(h + hk) * hd].reshape(t, hk, hd)
        v = qkv[:, (h + hk) * hd:].reshape(t, hk, hd)
    else:
        k, v = kv
    # [v1, v2] of each K/V pair, 2 hd wide.
    both = v.reshape(t, hk // 2, 2 * hd)
    scale = float(hd) ** -0.5
    a1 = attention(q[:, 0::2], k[:, 0::2], both, scale, window)
    a2 = attention(q[:, 1::2], k[:, 1::2], both, scale, window)
    lam0 = lambda_init(i)
    lam = (jnp.exp(jnp.sum(c.up(w[p + "lambda_q1"]) * c.up(w[p + "lambda_k1"])))
           - jnp.exp(jnp.sum(c.up(w[p + "lambda_q2"]) * c.up(w[p + "lambda_k2"]))) + lam0)
    o = c.rms_norm(a1 - lam * a2, w[p + "subln"], cfg["layer_norm_eps"]) * (1.0 - lam0)
    return c.mm(o.reshape(t, h * hd), w[p + "o"]) + c.up(w[p + "o_bias"]), (k, v)


def mamba_inputs(w, p, cfg, u):
    """(a, z, dt [T,inner], B, C [T,d_state]): what the recurrence and the
    gate read."""
    inner, n, kk, rank = mamba_dims(cfg)
    t = u.shape[0]
    az = c.mm(u, w[p + "in_proj"])
    a, z = az[:, :inner], az[:, inner:]
    past = jnp.concatenate([jnp.zeros((kk - 1, inner), c.F32), a])
    a = c.silu(sum(past[j:j + t] * c.up(w[p + "conv"])[j] for j in range(kk)) + c.up(w[p + "conv_bias"]))
    rbc = c.mm(a, w[p + "x_proj"])
    dt = jax.nn.softplus(c.mm(rbc[:, :rank], w[p + "dt_proj"]) + dt_bias(w[p + "dt_draw"]))
    return a, z, dt, rbc[:, rank:rank + n], rbc[:, rank + n:]


def recurrence(cfg, a, dt, b_in, c_in):
    """The selective scan, one token at a time from S = 0. Returns S C
    [T, inner], without the skip."""
    neg_a = -jnp.exp(a_log(cfg))

    def step(s, xs):
        a_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * neg_a) * s + (dt_t * a_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    return jax.lax.scan(step, jnp.zeros(neg_a.shape, c.F32), (a, dt, b_in, c_in))[1]


def mamba(w, p, cfg, u):
    """Returns (out, y): y the scan's output with the skip, before the gate."""
    a, z, dt, b_in, c_in = mamba_inputs(w, p, cfg, u)
    y = recurrence(cfg, a, dt, b_in, c_in) + c.up(w[p + "D"]) * a
    return c.mm(y * c.silu(z), w[p + "out_proj"]), y


def mlp(w, p, cfg, x):
    f = cfg["intermediate_size"]

    def rows(xb):
        gv = c.mm(xb, w[p + "w1"])
        return c.mm(c.silu(gv[:, :f]) * gv[:, f:], w[p + "w2"])

    t = x.shape[0]
    if t <= ROW_BLOCK or t % ROW_BLOCK:
        return rows(x)
    return jax.lax.map(rows, x.reshape(t // ROW_BLOCK, ROW_BLOCK, -1)).reshape(x.shape)


def layer(w, i: int, cfg: dict, x, carried: dict):
    """One layer; ``carried`` holds the memory and layer n/2 + 1's (k, v)
    once they exist."""
    p, kind, eps = f"layers.{i}.", layer_kinds(cfg)[i], cfg["layer_norm_eps"]
    u = layer_norm(x, w, p + "norm1", eps)
    if kind in ("mamba", "memory"):
        mix, y = mamba(w, p + "mamba.", cfg, u)
        if kind == "memory":
            carried["memory"] = y
    elif kind == "gmu":
        mix = c.mm(carried["memory"] * c.silu(c.mm(u, w[p + "gmu.in_proj"])), w[p + "gmu.out_proj"])
    elif kind == "cross":
        mix, _ = diff_attention(w, p + "attn.", cfg, i, u, kv=carried["kv"])
    else:
        mix, kv = diff_attention(w, p + "attn.", cfg, i, u, window=cfg["sliding_window"] if kind == "window" else None)
        if kind == "full":
            carried["kv"] = kv
    h = x + mix
    return h + mlp(w, p + "mlp.", cfg, layer_norm(h, w, p + "norm2", eps))


def head(h, embed):
    """h @ embed^T in float32, a slice of the vocabulary at a time."""
    v = embed.shape[0]
    if v % HEAD_BLOCKS or v // HEAD_BLOCKS < 1024:
        return c.mm(h, embed.T)
    width = v // HEAD_BLOCKS

    def columns(i):
        return c.mm(h, jax.lax.dynamic_slice_in_dim(embed, i * width, width, axis=0).T)

    blocks = jax.lax.map(columns, jnp.arange(HEAD_BLOCKS))
    return jnp.moveaxis(blocks, 0, 1).reshape(h.shape[0], v)


def logits(w, cfg: dict, tokens, at):
    """Next-token logits after the positions ``at[:MAX_AT]``, and a
    routing margin of ``inf`` for each (a dense model routes nothing)."""
    at = at[:MAX_AT]
    with jax.default_matmul_precision("highest"):
        x = c.up(w["embed"][tokens])
        carried = {}
        for i in range(cfg["num_hidden_layers"]):
            x = layer(w, i, cfg, x, carried)
        h = layer_norm(x, w, "final_norm", cfg["layer_norm_eps"])
        return head(h[at], w["embed"]), jnp.full(at.shape, jnp.inf)
