"""Plain forward pass of the Falcon-H1 family, from its published
``config.json`` (huggingface.co/tiiuae/Falcon-H1-34B-Instruct) and, for
what the ``mamba_*`` keys name, Mamba-2 (arXiv:2405.21060). EVERY layer
runs a state-space mixer and grouped-query attention side by side on one
normed input, then a dense SwiGLU MLP. ``x`` is [T, hidden]; every
multiplier is the config's, applied where shown:

    h0   = embed[tokens] * embedding_multiplier
    per layer:  u = RMSNorm(h; attn_norm)
                h = h + SSM(u * ssm_in_multiplier) * ssm_out_multiplier
                      + ATT(u * attention_in_multiplier) * attention_out_multiplier
                h = h + MLP(RMSNorm(h; mlp_norm))
    logits = (RMSNorm(h; final_norm) W_head) * lm_head_multiplier

**ATT(a)**: q = a W_q [H x hd], k = (a W_k) * key_multiplier [Hk x hd],
v = a W_v; rotary (rotate-half, ``rope_theta``, all hd dims) on q and k;
causal softmax(q k^T * hd^-0.5) v, a K/V head serving H / Hk query
heads; W_o. No bias.

**MLP(m)**: (silu((m W_gate) * mlp_multipliers[0]) * (m W_up)) W_down *
mlp_multipliers[1].

**SSM(s)**, Hs heads of P channels, state size N, G groups, TOKEN BY TOKEN:
  p = (s W_in) * mup, W_in [hidden -> Hs P + (Hs P + 2 G N) + Hs] split in
  that order into z, xBC, dt; mup scales the columns of z, x, B, C, dt by
  ``ssm_multipliers[0..4]``. (W_in is kept here as its five column
  groups ``in_z``, ``in_x``, ``in_B``, ``in_C``, ``in_dt``, each drawn
  for its own multiplier; the adapter joins them in that order.)
  xBC = silu(conv(xBC) + b_conv): depthwise causal, kernel ``mamba_d_conv``;
  x [Hs, P], B [G, N], C [G, N] = split(xBC); head h uses group h // (Hs/G)
  delta_t[h] = softplus(dt_t[h] + dt_bias[h]);  a_t[h] = exp(-delta_t[h] exp(A_log[h]))
  S_t[h] = a_t[h] S_{t-1}[h] + delta_t[h] x_t[h] (outer) B_t[g]   S in R^{P x N}, float32, S_0 = 0
  y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
  y = y * silu(z)  (``mamba_norm_before_gate`` false: the gate first), then
  RMSNorm in G groups of Hs P / G channels times its [Hs P] scale
  (``mamba_rms_norm``); out = y W_out. No bias on the projections.

A_log, dt_bias from the seeded draws as Mamba-2 initialises them
(``decay_leaves``); D = 1. No experts, so no routing margin: every
position reads ``inf``. ``logits`` answers for at most ``MAX_AT``
positions, the head in blocks of ``HEAD_BLOCK`` columns.

Weights are named and shaped by this file ([in, out] matrices); it
imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c
from benchmarks.reference.mixtral import rope_half  # rotate-half over the whole head: one copy
# (A_log, dt_bias) from seeded unit-normal draws as Mamba-2 initialises
# them (A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1] through the
# inverse softplus), here a head: the same ranges and map, one copy. The
# adapter hands the program these very numbers.
from benchmarks.reference.solar_open2 import decay_leaves

FAMILY = "falcon_h1"
#: ``logits`` answers for at most this many leading entries of ``at``.
#: The harness pads ``at`` to the sequence's padded length and drops the
#: padding again; a [2048, 261120] float32 result would be 2.1 GB beside
#: 10.5 GB of weights. No cell's answers are longer.
MAX_AT = 512
#: Vocabulary columns per block of the head (17 blocks of the 261,120
#: words), so the float32 copy of the head's weights is 315 MB at a time
#: and not 5.3 GB.
HEAD_BLOCK = 15360
#: Standard deviation 0.25 for the convolution's seeded bias.
CONV_BIAS_FAN_IN = 16
INT8_KEEP = ("embed", "norm", "A_draw", "dt_draw", "conv", "ssm.D")


def ssm_dims(cfg: dict):
    """(heads, channels a head, state size, groups, conv kernel)."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if h * p != cfg["mamba_d_ssm"]:
        raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    if cfg["mamba_norm_before_gate"] or not cfg["mamba_rms_norm"] or cfg["mamba_proj_bias"]:
        raise ValueError("this reference covers the gate-first, grouped-norm, bias-free mixer")
    return h, p, cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"]


def weight_specs(cfg: dict) -> dict:
    """name -> (shape, fan_in). A matrix whose product meets a multiplier
    m is drawn with standard deviation fan_in ** -0.5 / m (fan_in x m^2
    here), so that scores, time steps, gates and logits have unit scale
    AFTER the multiplier: with plain fan-in scaling the published keys x
    0.011, logits x 1/128 and dt x 0.35 would flatten softmax, decays
    and logits until a wrong cache moved nothing."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sh, sp, sn, sg, kk = ssm_dims(cfg)
    inner, gn = sh * sp, sg * sn
    if cfg["attention_bias"] or cfg["mlp_bias"] or cfg["projectors_bias"] or not cfg["mamba_conv_bias"]:
        raise ValueError("this reference covers the family's bias-free projections with a biased convolution")
    if cfg["attn_layer_indices"] is not None or not cfg["mamba_use_mlp"]:
        raise ValueError("this reference covers attention and an MLP in every layer")
    m_in, m_ssm = cfg["ssm_in_multiplier"], cfg["ssm_multipliers"]
    m_gate, m_down = cfg["mlp_multipliers"]
    sq = lambda fan_in, m: fan_in * float(m) ** 2
    specs = {
        "embed": ((v, d), sq(1.0, cfg["embedding_multiplier"])),
        "final_norm": ((d,), 0),
        "lm_head": ((d, v), sq(d, cfg["lm_head_multiplier"])),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        specs.update({
            p + "attn_norm": ((d,), 0),
            p + "mlp_norm": ((d,), 0),
            p + "q_proj": ((d, h * hd), sq(d, cfg["attention_in_multiplier"])),
            p + "k_proj": ((d, hk * hd), sq(d, cfg["attention_in_multiplier"] * cfg["key_multiplier"])),
            p + "v_proj": ((d, hk * hd), sq(d, cfg["attention_in_multiplier"])),
            p + "o_proj": ((h * hd, d), sq(h * hd, cfg["attention_out_multiplier"])),
            p + "mlp.gate": ((d, f), sq(d, m_gate)),
            p + "mlp.up": ((d, f), d),
            p + "mlp.down": ((f, d), sq(f, m_down)),
            p + "ssm.in_z": ((d, inner), sq(d, m_in * m_ssm[0])),
            p + "ssm.in_x": ((d, inner), sq(d, m_in * m_ssm[1])),
            p + "ssm.in_B": ((d, gn), sq(d, m_in * m_ssm[2])),
            p + "ssm.in_C": ((d, gn), sq(d, m_in * m_ssm[3])),
            p + "ssm.in_dt": ((d, sh), sq(d, m_in * m_ssm[4])),
            p + "ssm.conv": ((kk, inner + 2 * gn), kk),
            p + "ssm.conv_bias": ((inner + 2 * gn,), CONV_BIAS_FAN_IN),
            # "Unit normal" leaves, as the embedding's draw is: what
            # ``decay_leaves`` maps onto A_log and dt_bias.
            p + "ssm.A_draw": ((sh,), -1),
            p + "ssm.dt_draw": ((sh,), -1),
            p + "ssm.D": ((sh,), 0),
            p + "ssm.norm": ((inner,), 0),
            p + "ssm.out_proj": ((inner, d), sq(inner, cfg["ssm_out_multiplier"])),
        })
    return specs


def attention(w, p, cfg, a, positions):
    t = a.shape[0]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    if cfg["rope_scaling"] is not None:
        raise ValueError("this reference covers the family's plain rotary configs")
    theta = float(cfg["rope_theta"])
    q = rope_half(c.mm(a, w[p + "q_proj"]).reshape(t, h, hd), positions, theta)
    k = rope_half((c.mm(a, w[p + "k_proj"]) * cfg["key_multiplier"]).reshape(t, hk, hd), positions, theta)
    v = c.mm(a, w[p + "v_proj"]).reshape(t, hk, hd)
    out = c.causal_attention(q, k, v, float(hd) ** -0.5)
    return c.mm(out.reshape(t, h * hd), w[p + "o_proj"])


def mlp(w, p, cfg, m):
    m_gate, m_down = cfg["mlp_multipliers"]
    gate = c.silu(c.mm(m, w[p + "mlp.gate"]) * m_gate)
    return c.mm(gate * c.mm(m, w[p + "mlp.up"]), w[p + "mlp.down"]) * m_down


def ssm_inputs(w, p, cfg, s):
    """(z [T, Hs P], x [T,Hs,P], B, C [T,G,N], delta [T,Hs], A [Hs]) of
    one mixer: everything the recurrence and the gate read."""
    t = s.shape[0]
    sh, sp, sn, sg, kk = ssm_dims(cfg)
    m = cfg["ssm_multipliers"]
    z, x, b_in, c_in, dt = (
        c.mm(s, w[p + "ssm.in_" + n]) * m[i] for i, n in enumerate(("z", "x", "B", "C", "dt"))
    )
    xbc = jnp.concatenate([x, b_in, c_in], axis=-1)
    cw = c.up(w[p + "ssm.conv"])
    past = jnp.concatenate([jnp.zeros((kk - 1, xbc.shape[1]), c.F32), xbc])
    xbc = c.silu(sum(past[j:j + t] * cw[j] for j in range(kk)) + c.up(w[p + "ssm.conv_bias"]))
    inner, gn = sh * sp, sg * sn
    x, b_in, c_in = xbc[:, :inner], xbc[:, inner:inner + gn], xbc[:, inner + gn:]
    a_log, dt_bias = decay_leaves(w[p + "ssm.A_draw"], w[p + "ssm.dt_draw"])
    delta = jax.nn.softplus(dt + dt_bias)
    return z, x.reshape(t, sh, sp), b_in.reshape(t, sg, sn), c_in.reshape(t, sg, sn), delta, jnp.exp(a_log)


def recurrence(x, b_in, c_in, delta, a_rate, d_skip):
    """The state-space recurrence, one token at a time from S = 0.
    Returns y [T,Hs,P]."""
    h, p = x.shape[1:]
    g, n = b_in.shape[1:]

    def step(s, xs):
        x_t, b_t, c_t, d_t = xs
        b_h, c_h = jnp.repeat(b_t, h // g, axis=0), jnp.repeat(c_t, h // g, axis=0)  # [Hs,N]
        s = s * jnp.exp(-d_t * a_rate)[:, None, None] + (d_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_h) + d_skip[:, None] * x_t

    return jax.lax.scan(step, jnp.zeros((h, p, n), c.F32), (x, b_in, c_in, delta))[1]


def ssm(w, p, cfg, s):
    t = s.shape[0]
    sh, sp, _, sg, _ = ssm_dims(cfg)
    z, x, b_in, c_in, delta, a_rate = ssm_inputs(w, p, cfg, s)
    y = recurrence(x, b_in, c_in, delta, a_rate, c.up(w[p + "ssm.D"])).reshape(t, sh * sp)
    y = (y * c.silu(z)).reshape(t, sg, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return c.mm(y.reshape(t, -1) * c.up(w[p + "ssm.norm"]), w[p + "ssm.out_proj"])


def layer(w, i: int, cfg: dict, x, positions):
    p = f"layers.{i}."
    eps = cfg["rms_norm_eps"]
    u = c.rms_norm(x, w[p + "attn_norm"], eps)
    x = (
        x
        + ssm(w, p, cfg, u * cfg["ssm_in_multiplier"]) * cfg["ssm_out_multiplier"]
        + attention(w, p, cfg, u * cfg["attention_in_multiplier"], positions) * cfg["attention_out_multiplier"]
    )
    return x + mlp(w, p, cfg, c.rms_norm(x, w[p + "mlp_norm"], eps))


def head(h, lm_head):
    """h @ lm_head in float32, ``HEAD_BLOCK`` vocabulary columns at a time."""
    v = lm_head.shape[1]
    if v <= HEAD_BLOCK or v % HEAD_BLOCK:
        return c.mm(h, lm_head)
    blocks = jax.lax.map(
        lambda i: c.mm(h, jax.lax.dynamic_slice_in_dim(lm_head, i * HEAD_BLOCK, HEAD_BLOCK, axis=1)),
        jnp.arange(v // HEAD_BLOCK),
    )
    return jnp.moveaxis(blocks, 0, 1).reshape(h.shape[0], v)


def logits(w, cfg: dict, tokens, at):
    """Next-token logits after the positions ``at[:MAX_AT]``, and a
    routing margin of ``inf`` for each (a dense model routes nothing)."""
    at = at[:MAX_AT]
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0])
        x = c.up(w["embed"][tokens]) * cfg["embedding_multiplier"]
        for i in range(cfg["num_hidden_layers"]):
            x = layer(w, i, cfg, x, positions)
        h = c.rms_norm(x, w["final_norm"], cfg["rms_norm_eps"])
        return head(h[at], w["lm_head"]) * cfg["lm_head_multiplier"], jnp.full(at.shape, jnp.inf)
