"""Plain forward pass of DeepSeek-V2 (arXiv:2405.04434; layer equations as
executed by transformers' ``modeling_deepseek_v2.py``): multi-head latent
attention in its expanded form, decoupled interleaved RoPE with YaRN
frequencies, a dense SwiGLU in the first ``first_k_dense_replace`` layers
and, after them, softmax-scored greedy top-k routed experts (raw gate mass,
``norm_topk_prob`` false) plus always-on shared experts.

Departures from the published description: none in the equations. The
YaRN attention factor is mscale(factor, mscale) / mscale(factor,
mscale_all_dim), which the published config (0.707 for both) makes 1, and
the softmax scale is qk_head_dim ** -0.5, as transformers runs it.

Weights are named and shaped by this file ([in, out] matrices, experts
stacked [E, in, out]); it imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c

FAMILY = "deepseek_v2"


def weight_specs(cfg: dict) -> dict:
    """name -> (shape, fan_in); fan_in 0 marks a norm scale (ones) and -1
    the embedding (unit normal)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    kvr, v = cfg["kv_lora_rank"], cfg["vocab_size"]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("this reference covers the no-q-LoRA models (V2-Lite)")
    specs = {"embed": ((v, d), -1), "final_norm": ((d,), 0), "lm_head": ((d, v), d)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        specs.update({
            p + "attn_norm": ((d,), 0),
            p + "q_proj": ((d, h * (dn + dr)), d),
            p + "kv_a_proj": ((d, kvr + dr), d),
            p + "kv_a_norm": ((kvr,), 0),
            p + "kv_b_proj": ((kvr, h * (dn + dv)), kvr),
            p + "o_proj": ((h * dv, d), h * dv),
            p + "mlp_norm": ((d,), 0),
        })
        if i < cfg["first_k_dense_replace"]:
            ff = cfg["intermediate_size"]
            specs.update({
                p + "mlp.gate": ((d, ff), d),
                p + "mlp.up": ((d, ff), d),
                p + "mlp.down": ((ff, d), ff),
            })
        else:
            specs.update({
                p + "moe.router": ((d, e), d),
                p + "moe.experts.gate": ((e, d, f), d),
                p + "moe.experts.up": ((e, d, f), d),
                p + "moe.experts.down": ((e, f, d), f),
                p + "moe.shared.gate": ((d, fs), d),
                p + "moe.shared.up": ((d, fs), d),
                p + "moe.shared.down": ((fs, d), fs),
            })
    return specs


#: Weights the int8 control leaves alone, as int8 weight-only serving does:
#: embedding, norms, routers and the latent up-projection.
INT8_KEEP = ("embed", "norm", "router", "kv_b_proj")


def yarn_inv_freq(dim: int, theta: float, s: dict):
    """YaRN (arXiv:2309.00071) inverse frequencies: interpolated below the
    ramp, unscaled above it."""
    pos = theta ** (jnp.arange(0, dim, 2, dtype=c.F32) / dim)
    extrapolated, interpolated = 1.0 / pos, 1.0 / (s["factor"] * pos)
    orig = s["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(s["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=c.F32) - low) / (high - low), 0.0, 1.0)
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def yarn_attention_factor(s: dict) -> float:
    def mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    if s.get("mscale") and s.get("mscale_all_dim"):
        return mscale(s["factor"], s["mscale"]) / mscale(s["factor"], s["mscale_all_dim"])
    return mscale(s["factor"], 1.0)


def rope_interleaved(x, positions, cfg):
    """x [T, heads, dr]: pairs (x[2i], x[2i+1]) rotate together."""
    dim = x.shape[-1]
    s = cfg.get("rope_scaling")
    if s:
        inv, factor = yarn_inv_freq(dim, cfg["rope_theta"], s), yarn_attention_factor(s)
    else:
        inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, dim, 2, dtype=c.F32) / dim))
        factor = 1.0
    ang = positions.astype(c.F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :] * factor, jnp.sin(ang)[:, None, :] * factor
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def attention(w, p, cfg, x, positions):
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    kvr = cfg["kv_lora_rank"]
    q = c.mm(x, w[p + "q_proj"]).reshape(t, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope_interleaved(q[..., dn:], positions, cfg)], -1)
    ckv_kr = c.mm(x, w[p + "kv_a_proj"])
    c_kv = c.rms_norm(ckv_kr[:, :kvr], w[p + "kv_a_norm"], cfg["rms_norm_eps"])
    k_pe = rope_interleaved(ckv_kr[:, None, kvr:], positions, cfg)
    kv = (c_kv @ c.up(w[p + "kv_b_proj"])).reshape(t, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (t, h, dr))], -1)
    out = c.causal_attention(q, k, kv[..., dn:], float(dn + dr) ** -0.5)
    return c.mm(out.reshape(t, h * dv), w[p + "o_proj"])


def moe(w, p, cfg, x):
    probs = jax.nn.softmax(x @ c.up(w[p + "moe.router"]), axis=-1)
    gates = c.topk_gates(probs, cfg["num_experts_per_tok"], cfg["norm_topk_prob"])
    routed = c.routed_experts(
        x, gates, w[p + "moe.experts.gate"], w[p + "moe.experts.up"], w[p + "moe.experts.down"]
    )
    shared = c.swiglu(x, w[p + "moe.shared.gate"], w[p + "moe.shared.up"], w[p + "moe.shared.down"])
    margin = c.routing_margin(probs, cfg["num_experts_per_tok"])
    return routed * cfg["routed_scaling_factor"] + shared, margin


def layer(w, i: int, cfg: dict, x, positions):
    p = f"layers.{i}."
    eps = cfg["rms_norm_eps"]
    x = x + attention(w, p, cfg, c.rms_norm(x, w[p + "attn_norm"], eps), positions)
    h = c.rms_norm(x, w[p + "mlp_norm"], eps)
    if i < cfg["first_k_dense_replace"]:
        y = c.swiglu(h, w[p + "mlp.gate"], w[p + "mlp.up"], w[p + "mlp.down"])
        return x + y, jnp.full(x.shape[:1], jnp.inf)
    y, margin = moe(w, p, cfg, h)
    return x + y, margin


def logits(w, cfg: dict, tokens, at):
    """Next-token logits after the positions ``at`` and their routing
    margins: ``common.logits`` over this family's ``layer``."""
    return c.logits(layer, w, cfg, tokens, at)
