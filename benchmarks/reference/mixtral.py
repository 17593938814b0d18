"""Plain forward pass of Mixtral (arXiv:2401.04088; equations as executed
by transformers' ``modeling_mixtral.py``): grouped-query attention with
split-half RoPE, and in every layer a softmax router whose top-k
probabilities are renormalised over SwiGLU experts. No sliding window
(the 8x7B config publishes ``sliding_window: null``).

Weights are named and shaped by this file ([in, out] matrices, experts
stacked [E, in, out]); it imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c

FAMILY = "mixtral"


def weight_specs(cfg: dict) -> dict:
    d, h, hk = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    f, e, v = cfg["intermediate_size"], cfg["num_local_experts"], cfg["vocab_size"]
    specs = {"embed": ((v, d), -1), "final_norm": ((d,), 0), "lm_head": ((d, v), d)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        specs.update({
            p + "attn_norm": ((d,), 0),
            p + "q_proj": ((d, h * hd), d),
            p + "k_proj": ((d, hk * hd), d),
            p + "v_proj": ((d, hk * hd), d),
            p + "o_proj": ((h * hd, d), h * hd),
            p + "moe_norm": ((d,), 0),
            p + "moe.router": ((d, e), d),
            p + "moe.experts.gate": ((e, d, f), d),
            p + "moe.experts.up": ((e, d, f), d),
            p + "moe.experts.down": ((e, f, d), f),
        })
    return specs


INT8_KEEP = ("embed", "norm", "router")


def rope_half(x, positions, theta):
    """x [T, heads, hd]: dimension i rotates with dimension i + hd/2."""
    dim = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=c.F32) / dim))
    ang = positions.astype(c.F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(w, p, cfg, x, positions):
    t = x.shape[0]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = rope_half(c.mm(x, w[p + "q_proj"]).reshape(t, h, hd), positions, cfg["rope_theta"])
    k = rope_half(c.mm(x, w[p + "k_proj"]).reshape(t, hk, hd), positions, cfg["rope_theta"])
    v = c.mm(x, w[p + "v_proj"]).reshape(t, hk, hd)
    out = c.causal_attention(q, k, v, float(hd) ** -0.5)
    return c.mm(out.reshape(t, h * hd), w[p + "o_proj"])


def moe(w, p, cfg, x):
    probs = jax.nn.softmax(x @ c.up(w[p + "moe.router"]), axis=-1)
    gates = c.topk_gates(probs, cfg["num_experts_per_tok"], True)
    y = c.routed_experts(
        x, gates, w[p + "moe.experts.gate"], w[p + "moe.experts.up"], w[p + "moe.experts.down"]
    )
    return y, c.routing_margin(probs, cfg["num_experts_per_tok"])


def layer(w, i: int, cfg: dict, x, positions):
    p = f"layers.{i}."
    eps = cfg["rms_norm_eps"]
    x = x + attention(w, p, cfg, c.rms_norm(x, w[p + "attn_norm"], eps), positions)
    y, margin = moe(w, p, cfg, c.rms_norm(x, w[p + "moe_norm"], eps))
    return x + y, margin


def logits(w, cfg: dict, tokens, at):
    """Next-token logits after the positions ``at`` and their routing
    margins: ``common.logits`` over this family's ``layer``."""
    return c.logits(layer, w, cfg, tokens, at)
