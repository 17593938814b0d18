"""Plain forward pass of the Solar-Open2 family, from its published
``config.json`` (huggingface.co/upstage/Solar-Open2-250B) and, for what
the ``kda_*`` keys name, Kimi Linear (arXiv:2510.26692). Layers repeat a
period: the layers in ``gqa_layers`` are softmax attention, the others
linear attention; every layer ends in routed experts beside a shared
one. ``x`` is [T, hidden]; RMSNorm before each half, residual after.

**Linear-attention layer** (Kimi Delta Attention), H heads of d = 128,
no positions, TOKEN BY TOKEN:
  q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v));
  conv is a depthwise causal convolution over time, kernel 4, no bias:
  y_t[c] = sum_j w[j, c] u_{t-3+j}[c];
  q, k L2-normalised per head (a / sqrt(sum a^2 + 1e-6)), q times d^-0.5;
  g_t = -exp(A_log[h]) * softplus((x W_f1) W_f2 + dt_bias)   [H, d], <= 0
  (A_log, dt_bias from the seeded draws as Kimi Linear initialises them:
  ``decay_leaves``)
  beta_t = 2 sigmoid(x W_b)                                  [H]
  per head, S in R^{d x d}, float32, S_0 = 0:
    S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t
  out = (RMSNorm_head(o_t) * sigmoid((x W_g1) W_g2)) W_o.

**Softmax layer**: grouped-query causal attention, no rotary or other
position signal, scale head_dim^-0.5; out = (attn * sigmoid(x W_gate)) W_o.

**Expert layer**: s = sigmoid(x W_r) over the router's width; the k
largest of s + b are chosen (selection bias b), weighed s_i / sum of the
chosen s, times ``routed_scaling_factor``; the result is the weighted
SwiGLU experts AMONG THOSE HELD HERE plus the shared SwiGLU expert.
``n_routed_experts`` counts the experts held here, the router's first
ones; ``n_routed_experts_published`` is the router's width (absent: all
experts are here). What the experts held on other chips would add is
left out, here as in the program.

Weights are named and shaped by this file ([in, out] matrices, experts
stacked [E, in, out]); it imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c

FAMILY = "solar_open2"
#: ``logits`` answers for at most this many leading entries of ``at``.
#: The harness pads ``at`` to the sequence's padded length and drops
#: the padding again; a [8192, 196608] float32 result would be 6.4 GB
#: beside 9.4 GB of weights. No cell's answers are longer.
MAX_AT = 1024
#: Vocabulary columns per block of the head, so the float32 copy of the
#: head's weights is 268 MB at a time and not 3.2 GB.
HEAD_BLOCK = 16384
#: Standard deviation of the seeded selection bias (fan_in ** -0.5).
BIAS_FAN_IN = 10_000
INT8_KEEP = ("embed", "norm", "router", "A_draw", "dt_draw", "conv")
#: Kimi Linear's initial ranges (its KDA layer, as Mamba-2's): the decay
#: rate A uniform over heads, the time step dt log-uniform over channels.
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


def router_width(cfg: dict) -> int:
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def layer_kind(cfg: dict, i: int) -> str:
    return "gqa" if i in cfg["gqa_layers"] else "kda"


def decay_leaves(a_draw, dt_draw):
    """(A_log [H], dt_bias [H*d]) in float32 from the seeded unit-normal
    draws, as Kimi Linear initialises them: with u = Phi(draw) uniform,
    A = 1 + 15 u and A_log = log A; dt = 1e-3 * 100 ** u and dt_bias the
    inverse softplus of dt. So softplus(dt_bias) is the channel's time
    step, and a channel forgets at about A * dt a token before the
    input's own term: from a few tokens of memory to several hundred.
    The adapter hands the program these very numbers."""
    u = lambda z: jax.scipy.stats.norm.cdf(c.up(z))
    a = A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * u(a_draw)
    dt = DT_RANGE[0] * (DT_RANGE[1] / DT_RANGE[0]) ** u(dt_draw)
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def weight_specs(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    la = cfg["linear_attn_config"]
    lh, ld, kk = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    lc = lh * ld
    if cfg["kda_use_full_proj"]:
        raise ValueError("this reference covers the family's low-rank (kda_use_full_proj: false) configs")
    rank = ld  # the decay and the output gate go through rank head_dim
    f, held, ns = cfg["moe_intermediate_size"], cfg["n_routed_experts"], cfg["n_shared_experts"]
    specs = {"embed": ((v, d), -1), "final_norm": ((d,), 0), "lm_head": ((d, v), d)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        specs.update({
            p + "attn_norm": ((d,), 0),
            p + "mlp_norm": ((d,), 0),
            p + "moe.router": ((d, router_width(cfg)), d),
            p + "moe.router_bias": ((router_width(cfg),), BIAS_FAN_IN),
            p + "moe.experts.gate": ((held, d, f), d),
            p + "moe.experts.up": ((held, d, f), d),
            p + "moe.experts.down": ((held, f, d), f),
            p + "moe.shared.gate": ((d, f * ns), d),
            p + "moe.shared.up": ((d, f * ns), d),
            p + "moe.shared.down": ((f * ns, d), f * ns),
        })
        if layer_kind(cfg, i) == "gqa":
            specs.update({
                p + "q_proj": ((d, h * hd), d),
                p + "k_proj": ((d, hk * hd), d),
                p + "v_proj": ((d, hk * hd), d),
                p + "gate_proj": ((d, h * hd), d),
                p + "o_proj": ((h * hd, d), h * hd),
            })
            continue
        specs.update({
            p + "kda.f_a": ((d, rank), d),
            p + "kda.f_b": ((rank, lc), rank),
            p + "kda.g_a": ((d, rank), d),
            p + "kda.g_b": ((rank, lc), rank),
            p + "kda.beta": ((d, lh), d),
            # "Unit normal" leaves, as the embedding is: the draws that
            # ``decay_leaves`` maps onto A_log and dt_bias.
            p + "kda.A_draw": ((lh,), -1),
            p + "kda.dt_draw": ((lc,), -1),
            p + "kda.o_norm": ((ld,), 0),
            p + "kda.o_proj": ((lc, d), lc),
        })
        for n in "qkv":
            specs[p + f"kda.{n}_proj"] = ((d, lc), d)
            specs[p + f"kda.{n}_conv"] = ((kk, lc), kk)
    return specs


def attention(w, p, cfg, x):
    t = x.shape[0]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    if cfg["use_rope"]:
        raise ValueError("this reference covers the family's NoPE configs")
    q = c.mm(x, w[p + "q_proj"]).reshape(t, h, hd)
    k = c.mm(x, w[p + "k_proj"]).reshape(t, hk, hd)
    v = c.mm(x, w[p + "v_proj"]).reshape(t, hk, hd)
    out = c.causal_attention(q, k, v, float(hd) ** -0.5).reshape(t, h * hd)
    if cfg["use_gqa_gate"]:
        out = out * jax.nn.sigmoid(c.mm(x, w[p + "gate_proj"]))
    return c.mm(out, w[p + "o_proj"])


def kda_inputs(w, p, cfg, x):
    """(q, k, v, g [T,H,d], beta [T,H]) of one linear-attention layer."""
    t = x.shape[0]
    la = cfg["linear_attn_config"]
    h, d, kk = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]

    def conv(n):
        u = c.mm(x, w[p + f"kda.{n}_proj"])
        cw = c.up(w[p + f"kda.{n}_conv"])
        past = jnp.concatenate([jnp.zeros((kk - 1, u.shape[1]), c.F32), u])
        y = sum(past[j:j + t] * cw[j] for j in range(kk))
        return c.silu(y).reshape(t, h, d)

    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k, v = unit(conv("q")) * float(d) ** -0.5, unit(conv("k")), conv("v")
    a_log, dt_bias = decay_leaves(w[p + "kda.A_draw"], w[p + "kda.dt_draw"])
    pre = c.mm(c.mm(x, w[p + "kda.f_a"]), w[p + "kda.f_b"]) + dt_bias
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(pre.reshape(t, h, d))
    beta = jax.nn.sigmoid(c.mm(x, w[p + "kda.beta"]))
    return q, k, v, g, beta * (2.0 if cfg["kda_allow_neg_eigval"] else 1.0)


def delta_rule(q, k, v, g, beta):
    """The recurrence, one token at a time from S = 0. Returns o [T,H,d]."""

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]
        delta = v_t - jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + (b_t[:, None] * k_t)[..., None] * delta[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    h, d = q.shape[1:]
    return jax.lax.scan(step, jnp.zeros((h, d, d), c.F32), (q, k, v, g, beta))[1]


def kda(w, p, cfg, x):
    t = x.shape[0]
    o = delta_rule(*kda_inputs(w, p, cfg, x))
    gate = jax.nn.sigmoid(c.mm(c.mm(x, w[p + "kda.g_a"]), w[p + "kda.g_b"]))
    o = c.rms_norm(o, w[p + "kda.o_norm"], cfg["rms_norm_eps"]) * gate.reshape(o.shape)
    return c.mm(o.reshape(t, -1), w[p + "kda.o_proj"])


def route(w, p, cfg, x):
    """Dense [T, width] gate matrix and how firmly each token chose."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ c.up(w[p + "moe.router"]))
    chosen_by = scores + c.up(w[p + "moe.router_bias"])
    _, idx = jax.lax.top_k(chosen_by, k)
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    vals = vals * cfg["routed_scaling_factor"]
    gates = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=c.F32) * vals[..., None], axis=1)
    return gates, c.routing_margin(chosen_by, k)


def moe(w, p, cfg, x, first: int = 0):
    """``first``: the first routed expert of the share computed (0: this
    chip's; the share test walks all eight)."""
    gates, margin = route(w, p, cfg, x)
    here = gates[:, first:first + cfg["n_routed_experts"]]
    y = c.routed_experts(x, here, w[p + "moe.experts.gate"], w[p + "moe.experts.up"], w[p + "moe.experts.down"])
    y = y + c.swiglu(x, w[p + "moe.shared.gate"], w[p + "moe.shared.up"], w[p + "moe.shared.down"])
    return y, margin


def layer(w, i: int, cfg: dict, x, positions=None):
    p = f"layers.{i}."
    eps = cfg["rms_norm_eps"]
    mixer = attention if layer_kind(cfg, i) == "gqa" else kda
    x = x + mixer(w, p, cfg, c.rms_norm(x, w[p + "attn_norm"], eps))
    y, margin = moe(w, p, cfg, c.rms_norm(x, w[p + "mlp_norm"], eps))
    return x + y, margin


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
    """Next-token logits after the positions ``at[:MAX_AT]`` and how
    firmly each of those positions was routed (the least routing margin
    over the layers)."""
    at = at[:MAX_AT]
    with jax.default_matmul_precision("highest"):
        x = c.up(w["embed"][tokens])
        margin = jnp.full(tokens.shape, jnp.inf)
        for i in range(cfg["num_hidden_layers"]):
            x, m = layer(w, i, cfg, x)
            margin = jnp.minimum(margin, m)
        h = c.rms_norm(x, w["final_norm"], cfg["rms_norm_eps"])
        return head(h[at], w["lm_head"]), margin[at]
