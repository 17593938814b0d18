"""Mixtral through the program: the program's configuration built from the
published keys, and the reference-named weights re-labelled as the
program's parameter tree."""

from __future__ import annotations

import jax.numpy as jnp

FAMILY = "mixtral"


def program_model(cfg: dict, assumed: dict):
    from tpufw.models.mixtral import Mixtral, MixtralConfig

    if cfg.get("sliding_window") is not None:
        raise ValueError("this adapter covers the windowless Mixtral configs")
    pc = MixtralConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        remat=False,
        scan_layers=False,
        n_experts=cfg["num_local_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        capacity_factor=cfg["num_local_experts"] / cfg["num_experts_per_tok"],
        moe_dispatch=assumed["moe_dispatch"],
    )
    return Mixtral, pc


def to_program(w: dict, cfg: dict) -> dict:
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    d = cfg["hidden_size"]
    k = lambda x: {"kernel": x}
    tree = {
        "embed": {"embedding": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": k(w["lm_head"]),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        tree[f"layer_{i}"] = {
            "attn_norm": {"scale": w[p + "attn_norm"]},
            "moe_norm": {"scale": w[p + "moe_norm"]},
            "attn": {
                "q": k(w[p + "q_proj"].reshape(d, h, hd)),
                "k": k(w[p + "k_proj"].reshape(d, hk, hd)),
                "v": k(w[p + "v_proj"].reshape(d, hk, hd)),
                "o": k(w[p + "o_proj"].reshape(h, hd, d)),
            },
            "moe": {
                "router": k(w[p + "moe.router"]),
                "w_gate": w[p + "moe.experts.gate"],
                "w_up": w[p + "moe.experts.up"],
                "w_down": w[p + "moe.experts.down"],
            },
        }
    return tree

