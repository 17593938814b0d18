"""Plain forward pass of the Olmo-Hybrid family, from its published
``config.json`` (huggingface.co/allenai/Olmo-Hybrid-7B) and, for what the
``linear_*`` keys name, Gated DeltaNet (arXiv:2412.06464, as ``fla``'s
``GatedDeltaNet`` and transformers' Qwen3-Next spell it). Dense; layers of
two kinds by ``layer_types``, the same block around both. ``x`` is
[T, hidden]:

    per layer:  h = x + RMSNorm(Mixer(x); mixer_norm)
                x = h + RMSNorm(MLP(h); mlp_norm)        MLP(h) = (silu(h W_gate) * h W_up) W_down
    logits = RMSNorm(x; final_norm) W_head

**full_attention**: q = RMSNorm(x W_q; q_norm), k = RMSNorm(x W_k; k_norm),
each over the WHOLE projected width (H x hd) before the split into heads;
v = x W_v; H = Hk heads of hd; NO rotary embedding; causal
softmax(q k^T * hd^-0.5) v; W_o. No bias.

**linear_attention**, Hl heads, keys of dk and values of dv, TOKEN BY TOKEN:
  q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v)): depthwise
  causal, kernel ``linear_conv_kernel_dim``, no bias, one kernel each;
  per head q = q / |q| * dk^-0.5, k = k / |k| (|a| = sqrt(sum a^2 + 1e-6));
  beta_t[h] = 2 sigmoid(x W_b)        (the 2: ``linear_allow_neg_eigval``)
  g_t[h] = -exp(A_log[h]) softplus(x W_a + dt_bias[h]);  alpha_t = exp(g_t)
  S' = alpha_t S_{t-1}                                   S in R^{dk x dv}, float32, S_0 = 0
  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
  o_t = S_t^T q_t
  o = RMSNorm(o; o_norm [dv], per head) * silu(x W_g);  out = o W_o.

Departures from the published description, each because ``config.json``
cannot carry it (the configuration file's ``assumed`` says the same):
- ``rope_theta`` null is read as NO rotary embedding in the full layers.
- The norms' places (after the mixer and after the MLP; QK-norm over the
  whole width) are OLMo 2's and OLMo 3's; the same block around both kinds.
- The linear layer's details the config does not name: separate q/k/v
  convolutions without bias, SiLU after them, L2-normalised q and k, q
  scaled by dk^-0.5, the output norm THEN the SiLU gate.
- A_log, dt_bias from the seeded draws as Mamba-2 initialises them
  (``decay_leaves``, a number a head).
- W_a, W_b and W_g, which read the un-normed residual stream, are drawn at
  ``fan_in * RESIDUAL_VARIANCE`` (see there).

No experts, so no routing margin: every position reads ``inf``. ``logits``
answers for at most ``MAX_AT`` positions, the cell's longest answer, the
head in blocks of ``HEAD_BLOCK`` columns over blocks of ``AT_BLOCK``
positions, so that a sequence's LAST answer positions are answered and not
only its first.

Weights are named and shaped by this file ([in, out] matrices); it
imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c
# (A_log, dt_bias) from seeded unit-normal draws as Mamba-2 initialises
# them (A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1] through the
# inverse softplus), here a head: the same ranges and map, one copy. The
# adapter hands the program these very numbers.
from benchmarks.reference.solar_open2 import decay_leaves

FAMILY = "olmo_hybrid"
#: ``logits`` answers for at most this many leading entries of ``at``: the
#: longest answer the family's cell asks for. The harness pads ``at`` to
#: the sequence's padded length and drops the padding again.
MAX_AT = 2048
#: Answer positions per block of the head: [512, 100352] float32 is 206 MB
#: at a time; the whole answer, 822 MB beside 8.2 GB of weights, is what
#: is returned.
AT_BLOCK = 512
#: Vocabulary columns per block of the head (7 blocks of the 100,352
#: words), so the float32 copy of the head's weights is 220 MB at a time
#: and not 1.5 GB.
HEAD_BLOCK = 14336
#: With seeded weights every normed branch adds one unit of variance to
#: the residual stream (norm scales are 1), so a layer's input has
#: variance 1 + 2 i: 1 at the first of sixteen layers, 31 at the last, 16
#: in the middle. The three projections that read that stream with NO
#: norm behind them (the decay's, the write strength's, the output
#: gate's) are drawn with this factor on their fan-in, so that their
#: pre-activations have unit scale at mid-depth: at plain fan-in the
#: decay's softplus would see inputs of scale 4-5.6 in the later layers
#: and forget in a token or two, until a lost state moved nothing.
RESIDUAL_VARIANCE = 16
INT8_KEEP = ("embed", "norm", "A_draw", "dt_draw", "conv")


def linear_dims(cfg: dict):
    """(heads, key channels a head, value channels a head, conv kernel)."""
    h = cfg["linear_num_key_heads"]
    if cfg["linear_num_value_heads"] != h:
        raise ValueError("this reference covers as many value heads as key heads")
    return h, cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_kinds(cfg: dict):
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {"linear_attention", "full_attention"}:
        raise ValueError("layer_types names linear_attention or full_attention for each layer")
    return kinds


def weight_specs(cfg: dict) -> dict:
    """name -> (shape, fan_in)."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    lh, dk, dv, kk = linear_dims(cfg)
    if cfg["attention_bias"] or cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu":
        raise ValueError("this reference covers the family's bias-free, untied, SiLU configs")
    if cfg["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("this reference covers full layers with no rotary embedding (rope_theta null)")
    specs = {"embed": ((v, d), -1), "final_norm": ((d,), 0), "lm_head": ((d, v), d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"layers.{i}."
        specs.update({
            p + "mixer_norm": ((d,), 0),
            p + "mlp_norm": ((d,), 0),
            p + "mlp.gate": ((d, f), d),
            p + "mlp.up": ((d, f), d),
            p + "mlp.down": ((f, d), f),
        })
        if kind == "full_attention":
            specs.update({
                p + "q_proj": ((d, h * hd), d),
                p + "k_proj": ((d, hk * hd), d),
                p + "v_proj": ((d, hk * hd), d),
                p + "q_norm": ((h * hd,), 0),
                p + "k_norm": ((hk * hd,), 0),
                p + "o_proj": ((h * hd, d), h * hd),
            })
            continue
        q = p + "gdn."
        specs.update({
            q + "q": ((d, lh * dk), d),
            q + "k": ((d, lh * dk), d),
            q + "v": ((d, lh * dv), d),
            q + "q_conv": ((kk, lh * dk), kk),
            q + "k_conv": ((kk, lh * dk), kk),
            q + "v_conv": ((kk, lh * dv), kk),
            q + "decay": ((d, lh), d * RESIDUAL_VARIANCE),
            q + "beta": ((d, lh), d * RESIDUAL_VARIANCE),
            q + "gate": ((d, lh * dv), d * RESIDUAL_VARIANCE),
            # "Unit normal" leaves, as the embedding's draw is: what
            # ``decay_leaves`` maps onto A_log and dt_bias.
            q + "A_draw": ((lh,), -1),
            q + "dt_draw": ((lh,), -1),
            q + "o_norm": ((dv,), 0),
            q + "o": ((lh * dv, d), lh * dv),
        })
    return specs


def attention(w, p, cfg, x):
    t = x.shape[0]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    eps = cfg["rms_norm_eps"]
    q = c.rms_norm(c.mm(x, w[p + "q_proj"]), w[p + "q_norm"], eps).reshape(t, h, hd)
    k = c.rms_norm(c.mm(x, w[p + "k_proj"]), w[p + "k_norm"], eps).reshape(t, hk, hd)
    v = c.mm(x, w[p + "v_proj"]).reshape(t, hk, hd)
    out = c.causal_attention(q, k, v, float(hd) ** -0.5)
    return c.mm(out.reshape(t, h * hd), w[p + "o_proj"])


def short_conv(x, kernel):
    """Depthwise causal convolution from an empty past, then SiLU.
    x [T, C]; kernel [K, C]."""
    kk, t = kernel.shape[0], x.shape[0]
    past = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), c.F32), x])
    return c.silu(sum(past[j:j + t] * c.up(kernel)[j] for j in range(kk)))


def delta_inputs(w, p, cfg, x):
    """(q, k [T,Hl,dk], v [T,Hl,dv], g, beta [T,Hl]) of one linear layer:
    everything the recurrence reads."""
    t = x.shape[0]
    lh, dk, dv, _ = linear_dims(cfg)
    q, k, v = (short_conv(c.mm(x, w[p + n]), w[p + n + "_conv"]) for n in "qkv")
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q = unit(q.reshape(t, lh, dk)) * float(dk) ** -0.5
    k = unit(k.reshape(t, lh, dk))
    a_log, dt_bias = decay_leaves(w[p + "A_draw"], w[p + "dt_draw"])
    g = -jnp.exp(a_log) * jax.nn.softplus(c.mm(x, w[p + "decay"]) + dt_bias)
    beta = jax.nn.sigmoid(c.mm(x, w[p + "beta"])) * (2.0 if cfg["linear_allow_neg_eigval"] else 1.0)
    return q, k, v.reshape(t, lh, dv), g, beta


def recurrence(q, k, v, g, beta):
    """The gated delta rule, one token at a time from S = 0. Returns o
    [T,Hl,dv]."""
    h, dk = q.shape[1:]
    dv = v.shape[2]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        delta = v_t - jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + (b_t[:, None] * k_t)[:, :, None] * delta[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    return jax.lax.scan(step, jnp.zeros((h, dk, dv), c.F32), (q, k, v, g, beta))[1]


def gated_delta_net(w, p, cfg, x):
    t = x.shape[0]
    o = recurrence(*delta_inputs(w, p, cfg, x))
    o = c.rms_norm(o, w[p + "o_norm"], cfg["rms_norm_eps"])
    o = o.reshape(t, -1) * c.silu(c.mm(x, w[p + "gate"]))
    return c.mm(o, w[p + "o"])


def layer(w, i: int, cfg: dict, x):
    p = f"layers.{i}."
    eps = cfg["rms_norm_eps"]
    if layer_kinds(cfg)[i] == "full_attention":
        mix = attention(w, p, cfg, x)
    else:
        mix = gated_delta_net(w, p + "gdn.", cfg, x)
    h = x + c.rms_norm(mix, w[p + "mixer_norm"], eps)
    return h + c.rms_norm(c.swiglu(h, w[p + "mlp.gate"], w[p + "mlp.up"], w[p + "mlp.down"]), w[p + "mlp_norm"], eps)


def head(h, lm_head):
    """h @ lm_head in float32: ``AT_BLOCK`` positions by ``HEAD_BLOCK``
    vocabulary columns at a time."""
    n, v = h.shape[0], lm_head.shape[1]
    if v <= HEAD_BLOCK or v % HEAD_BLOCK or n <= AT_BLOCK or n % AT_BLOCK:
        return c.mm(h, lm_head)

    def columns(i):
        wb = jax.lax.dynamic_slice_in_dim(lm_head, i * HEAD_BLOCK, HEAD_BLOCK, axis=1)
        rows = jax.lax.map(lambda hb: c.mm(hb, wb), h.reshape(n // AT_BLOCK, AT_BLOCK, -1))
        return rows.reshape(n, HEAD_BLOCK)

    blocks = jax.lax.map(columns, jnp.arange(v // HEAD_BLOCK))
    return jnp.moveaxis(blocks, 0, 1).reshape(n, v)


def logits(w, cfg: dict, tokens, at):
    """Next-token logits after the positions ``at[:MAX_AT]``, and a
    routing margin of ``inf`` for each (a dense model routes nothing)."""
    at = at[:MAX_AT]
    with jax.default_matmul_precision("highest"):
        x = c.up(w["embed"][tokens])
        for i in range(cfg["num_hidden_layers"]):
            x = layer(w, i, cfg, x)
        h = c.rms_norm(x, w["final_norm"], cfg["rms_norm_eps"])
        return head(h[at], w["lm_head"]), jnp.full(at.shape, jnp.inf)
