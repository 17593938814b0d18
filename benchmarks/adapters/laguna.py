"""Laguna through the program: the program's configuration built from the
published keys, and the reference-named weights re-labelled as the
program's parameter tree."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference.laguna import FULL, SLIDING, check_covered

FAMILY = "laguna"


def layer_rope(r: dict, head_dim: int):
    """One entry of the published ``rope_parameters`` as the program's."""
    from tpufw.models.deepseek import YarnScaling
    from tpufw.models.llama import LayerRope

    dim = int(head_dim * r["partial_rotary_factor"])
    scaling = None
    if r["rope_type"] == "yarn":
        scaling = YarnScaling(
            factor=float(r["factor"]),
            original_max_position_embeddings=r["original_max_position_embeddings"],
            beta_fast=float(r["beta_fast"]),
            beta_slow=float(r["beta_slow"]),
            attention_factor=float(r["attention_factor"]),
        )
    elif r["rope_type"] != "default":
        raise ValueError(f"rope_type {r['rope_type']!r}")
    return LayerRope(theta=float(r["rope_theta"]), scaling=scaling, rotary_dim=None if dim == head_dim else dim)


def program_model(cfg: dict, assumed: dict):
    from tpufw.models.laguna import Laguna, LagunaConfig

    check_covered(cfg)
    held = cfg["num_experts"]
    width = cfg.get("num_experts_published", held)
    if cfg["shared_expert_intermediate_size"] != cfg["moe_intermediate_size"]:
        raise ValueError("this adapter covers one shared expert of the routed experts' width")
    pc = LagunaConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        rms_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        remat=False,
        layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
        sliding_window=cfg["sliding_window"],
        rope_full=layer_rope(cfg["rope_parameters"][FULL], cfg["head_dim"]),
        rope_sliding=layer_rope(cfg["rope_parameters"][SLIDING], cfg["head_dim"]),
        attn_output_gate="per_head",
        n_routed_experts=width,
        experts_per_token=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=1,
        routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        moe_scoring="softmax",
        experts_held=None if held == width else (0, held),
        # Dropless, as the published model is at inference.
        capacity_factor=width / cfg["num_experts_per_tok"],
        moe_dispatch=assumed["moe_dispatch"],
    )
    return Laguna, pc


def to_program(w: dict, cfg: dict) -> dict:
    d, hk, hd = cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"]
    k = lambda x: {"kernel": x}
    tree = {
        "embed": {"embedding": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": k(w["lm_head"]),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        h = cfg["num_attention_heads_per_layer"][i]
        layer = {
            "attn_norm": {"scale": w[p + "attn_norm"]},
            "mlp_norm": {"scale": w[p + "mlp_norm"]},
            "attn": {
                "q": k(w[p + "q_proj"].reshape(d, h, hd)),
                "k": k(w[p + "k_proj"].reshape(d, hk, hd)),
                "v": k(w[p + "v_proj"].reshape(d, hk, hd)),
                "gate": k(w[p + "gate_proj"]),
                "o": k(w[p + "o_proj"].reshape(h, hd, d)),
            },
        }
        if cfg["mlp_layer_types"][i] == "dense":
            layer["mlp"] = {n: k(w[p + "mlp." + n]) for n in ("gate", "up", "down")}
        else:
            layer["moe"] = {
                "routed": {
                    "router": k(w[p + "moe.router"]),
                    "w_gate": w[p + "moe.experts.gate"],
                    "w_up": w[p + "moe.experts.up"],
                    "w_down": w[p + "moe.experts.down"],
                },
                "shared": {n: k(w[p + "moe.shared." + n]) for n in ("gate", "up", "down")},
            }
        tree[f"layer_{i}"] = layer
    return tree
