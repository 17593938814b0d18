"""Plain forward pass of the Laguna family, from its published
``config.json`` (huggingface.co/poolside/Laguna-S-2.1). ``x`` is
[T, hidden]; RMSNorm before each half, residual after. Layer ``l`` has an
attention kind ``layer_types[l]``, ``num_attention_heads_per_layer[l]``
query heads over ``num_key_value_heads`` K/V heads (head h reads K/V head
h // (H_l / K)) and a feed-forward kind ``mlp_layer_types[l]``.

**Attention**: q, k, v projections without bias; rotary by layer kind from
``rope_parameters``: a ``full_attention`` layer rotates the first
``partial_rotary_factor`` x head_dim dimensions of each head (split-half
among themselves; the rest pass unrotated) with YaRN frequencies and its
cosines and sines scaled by the published ``attention_factor`` (the
frequencies are arXiv:2309.00071's as transformers'
``_compute_yarn_parameters`` executes them: ``deepseek_v2.yarn_inv_freq``
of this directory, over the rotated dimensions); a
``sliding_attention`` layer rotates the whole head with plain rope.
Scores q . k / sqrt(head_dim) over keys j <= i, softmax in float32; a
sliding layer keeps only 0 <= i - j < ``sliding_window``. Then a per-head
gate (``gating: per-head``): g = sigmoid(x W_g), one scalar a head, times
the head's output; then W_o.

**Feed-forward**: ``dense``: SwiGLU of ``intermediate_size``. ``sparse``:
router logits x W_r in float32, softmax over the router's width, the
``num_experts_per_tok`` largest kept and renormalised (``norm_topk_prob``),
times ``moe_routed_scaling_factor``, weighing the SwiGLU experts AMONG
THOSE HELD HERE; plus one shared SwiGLU expert for every token.
``num_experts`` counts the experts held here, the router's first ones;
``num_experts_published`` is the router's width (absent: all are here).
What the experts held on other chips would add is left out, here as in
the program.

Departures from the published description: none known. Where the config is
silent this file follows the configuration file's ``assumed`` block: the
gate is taken from the layer's normed input and multiplies before W_o;
softmax scoring without a selection bias; no QK-norm, no gate on the
shared expert; SiLU. ``logits`` answers for at most ``MAX_AT`` positions.

Weights are named and shaped by this file ([in, out] matrices, experts
stacked [E, in, out]); it imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c
from benchmarks.reference.deepseek_v2 import yarn_inv_freq  # YaRN's frequencies: the same paper's, one copy

FAMILY = "laguna"
#: ``logits`` answers for at most this many leading entries of ``at``
#: (as Solar-Open2's: the harness pads ``at`` to the sequence's padded
#: length, and [16384, 100352] float32 would be 6.6 GB). No cell's
#: answers are longer.
MAX_AT = 1024
#: Queries per attention block: [K/V heads, group, block, keys] float32
#: scores of a global layer over 16,384 keys are 0.8 GB at 48 heads.
QUERY_BLOCK = 256
INT8_KEEP = ("embed", "norm", "router")
FULL, SLIDING = "full_attention", "sliding_attention"


def router_width(cfg: dict) -> int:
    return cfg.get("num_experts_published", cfg["num_experts"])


def check_covered(cfg: dict) -> None:
    """The published switches this file implements one setting of."""
    n = cfg["num_hidden_layers"]
    covered = (
        cfg["gating"] == "per-head" and all(g == "per_head" for g in cfg["gating_types"])
        and not cfg["attention_bias"] and not cfg["moe_router_logit_softcapping"]
        and not cfg["moe_apply_router_weight_on_input"] and cfg["decoder_sparse_step"] == 1
        and all(len(cfg[k]) == n for k in ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer"))
        and [i for i, k in enumerate(cfg["mlp_layer_types"]) if k == "dense"] == list(cfg["mlp_only_layers"])
    )
    if not covered:
        raise ValueError("this reference covers the family's per-head-gated, bias-free, uncapped-router configs")


def weight_specs(cfg: dict) -> dict:
    check_covered(cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hk, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    f, fs, ff = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"], cfg["intermediate_size"]
    held = cfg["num_experts"]
    specs = {"embed": ((v, d), -1), "final_norm": ((d,), 0), "lm_head": ((d, v), d)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        h = cfg["num_attention_heads_per_layer"][i]
        specs.update({
            p + "attn_norm": ((d,), 0),
            p + "mlp_norm": ((d,), 0),
            p + "q_proj": ((d, h * hd), d),
            p + "k_proj": ((d, hk * hd), d),
            p + "v_proj": ((d, hk * hd), d),
            # Normal of standard deviation d ** -0.5 on a normed input:
            # gate logits of unit scale, gates spread over about 0.1-0.9.
            p + "gate_proj": ((d, h), d),
            p + "o_proj": ((h * hd, d), h * hd),
        })
        if cfg["mlp_layer_types"][i] == "dense":
            specs.update({
                p + "mlp.gate": ((d, ff), d),
                p + "mlp.up": ((d, ff), d),
                p + "mlp.down": ((ff, d), ff),
            })
            continue
        specs.update({
            # Router logits of unit scale too: the softmax over the width
            # is far from uniform and the top-k is no tie.
            p + "moe.router": ((d, router_width(cfg)), d),
            p + "moe.experts.gate": ((held, d, f), d),
            p + "moe.experts.up": ((held, d, f), d),
            p + "moe.experts.down": ((held, f, d), f),
            p + "moe.shared.gate": ((d, fs), d),
            p + "moe.shared.up": ((d, fs), d),
            p + "moe.shared.down": ((fs, d), fs),
        })
    return specs


def rope(x, positions, r: dict):
    """x [T, heads, hd] under one entry of ``rope_parameters``."""
    hd = x.shape[-1]
    dim = int(hd * r["partial_rotary_factor"])
    if r["rope_type"] == "yarn":
        inv, scale = yarn_inv_freq(dim, float(r["rope_theta"]), r), float(r["attention_factor"])
    elif r["rope_type"] == "default":
        inv, scale = 1.0 / (float(r["rope_theta"]) ** (jnp.arange(0, dim, 2, dtype=c.F32) / dim)), 1.0
    else:
        raise ValueError(f"rope_type {r['rope_type']!r}")
    ang = positions.astype(c.F32)[:, None] * inv
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[:, None, :]
    a, b, rest = x[..., : dim // 2], x[..., dim // 2: dim], x[..., dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def attend(q, k, v, scale, window=None):
    """q [T,H,d], k and v [T,K,d] -> [T,H,d]: causal softmax attention in
    blocks of queries, head h reading K/V head h // (H/K); with
    ``window``, a query at i sees the keys 0 <= i - j < window, and a
    block reads only the keys that any of its queries can see."""
    t, h, d = q.shape
    hk = k.shape[1]
    qb = min(QUERY_BLOCK, t)
    if t % qb:
        raise ValueError(f"sequence length {t} is not a multiple of {qb}")
    q = q.reshape(t, hk, h // hk, d)
    # Keys a block reads: all of them, or the window before its first
    # query and its own (front-padded so that every block reads as many).
    back = t if window is None else min(window, t)
    span = t if window is None else back + qb
    pad = 0 if window is None else back
    kp = jnp.concatenate([jnp.zeros((pad, hk, d), c.F32), k])
    vp = jnp.concatenate([jnp.zeros((pad, hk, d), c.F32), v])

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        first = 0 if window is None else i * qb
        ki = jax.lax.dynamic_slice_in_dim(kp, first, span, axis=0)
        vi = jax.lax.dynamic_slice_in_dim(vp, first, span, axis=0)
        key_pos = first - pad + jnp.arange(span)
        q_pos = i * qb + jnp.arange(qb)
        behind = q_pos[:, None] - key_pos[None, :]
        seen = (behind >= 0) & (key_pos[None, :] >= 0)
        if window is not None:
            seen = seen & (behind < window)
        s = jnp.einsum("qgrd,kgd->grqk", qi, ki) * scale
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, vi)

    return jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h, d)


def attention(w, p, cfg, i, x, positions):
    t = x.shape[0]
    kind = cfg["layer_types"][i]
    h, hk, hd = cfg["num_attention_heads_per_layer"][i], cfg["num_key_value_heads"], cfg["head_dim"]
    r = cfg["rope_parameters"][kind]
    q = rope(c.mm(x, w[p + "q_proj"]).reshape(t, h, hd), positions, r)
    k = rope(c.mm(x, w[p + "k_proj"]).reshape(t, hk, hd), positions, r)
    v = c.mm(x, w[p + "v_proj"]).reshape(t, hk, hd)
    out = attend(q, k, v, float(hd) ** -0.5, cfg["sliding_window"] if kind == SLIDING else None)
    out = out * jax.nn.sigmoid(c.mm(x, w[p + "gate_proj"]))[:, :, None]
    return c.mm(out.reshape(t, h * hd), w[p + "o_proj"])


def route(w, p, cfg, x):
    """Dense [T, width] gate matrix and how firmly each token chose."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ c.up(w[p + "moe.router"]), axis=-1)
    gates = c.topk_gates(probs, k, cfg["norm_topk_prob"]) * cfg["moe_routed_scaling_factor"]
    return gates, c.routing_margin(probs, k)


def moe(w, p, cfg, x, first: int = 0):
    """``first``: the first routed expert of the share computed (0: this
    chip's; the share test walks all eight)."""
    gates, margin = route(w, p, cfg, x)
    here = gates[:, first:first + cfg["num_experts"]]
    y = c.routed_experts(x, here, w[p + "moe.experts.gate"], w[p + "moe.experts.up"], w[p + "moe.experts.down"])
    y = y + c.swiglu(x, w[p + "moe.shared.gate"], w[p + "moe.shared.up"], w[p + "moe.shared.down"])
    return y, margin


def layer(w, i: int, cfg: dict, x, positions):
    p = f"layers.{i}."
    eps = cfg["rms_norm_eps"]
    x = x + attention(w, p, cfg, i, c.rms_norm(x, w[p + "attn_norm"], eps), positions)
    h = c.rms_norm(x, w[p + "mlp_norm"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        y = c.swiglu(h, w[p + "mlp.gate"], w[p + "mlp.up"], w[p + "mlp.down"])
        return x + y, jnp.full(x.shape[:1], jnp.inf)
    y, margin = moe(w, p, cfg, h)
    return x + y, margin


def logits(w, cfg: dict, tokens, at):
    """Next-token logits after the positions ``at[:MAX_AT]`` and how
    firmly each of those positions was routed (the least routing margin
    over the layers)."""
    check_covered(cfg)
    return c.logits(layer, w, cfg, tokens, at[:MAX_AT])
