"""Solar-Open2 through the program: the program's configuration built
from the published keys, and the reference-named weights re-labelled as
the program's parameter tree."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference.solar_open2 import decay_leaves

FAMILY = "solar_open2"


def program_model(cfg: dict, assumed: dict):
    from tpufw.models.solar_open2 import SolarOpen2, SolarOpen2Config

    if cfg["first_k_dense_replace"] or cfg["use_rope"] or cfg["kda_use_full_proj"]:
        raise ValueError("this adapter covers the family's NoPE, low-rank configs without a leading dense layer")
    la = cfg["linear_attn_config"]
    held = cfg["n_routed_experts"]
    width = cfg.get("n_routed_experts_published", held)
    n_layers = cfg["num_hidden_layers"]
    pc = SolarOpen2Config(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=n_layers,
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
        layer_types=tuple("gqa" if i in cfg["gqa_layers"] else "kda" for i in range(n_layers)),
        use_rope=False,
        attn_output_gate=cfg["use_gqa_gate"],
        kda_heads=la["num_heads"],
        kda_head_dim=la["head_dim"],
        kda_conv=la["short_conv_kernel_size"],
        kda_rank=la["head_dim"],
        kda_neg_eigval=cfg["kda_allow_neg_eigval"],
        n_routed_experts=width,
        experts_per_token=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        moe_scoring="sigmoid",
        experts_held=None if held == width else (0, held),
        # Dropless, as the published model is at inference.
        capacity_factor=width / cfg["num_experts_per_tok"],
        moe_dispatch=assumed["moe_dispatch"],
    )
    return SolarOpen2, pc


def to_program(w: dict, cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    la = cfg["linear_attn_config"]
    lh, ld = la["num_heads"], la["head_dim"]
    k = lambda x: {"kernel": x}
    tree = {
        "embed": {"embedding": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": k(w["lm_head"]),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        layer = {
            "attn_norm": {"scale": w[p + "attn_norm"]},
            "mlp_norm": {"scale": w[p + "mlp_norm"]},
            "moe": {
                "routed": {
                    "router": k(w[p + "moe.router"]),
                    "router_bias": w[p + "moe.router_bias"].astype(jnp.float32),
                    "w_gate": w[p + "moe.experts.gate"],
                    "w_up": w[p + "moe.experts.up"],
                    "w_down": w[p + "moe.experts.down"],
                },
                "shared": {n: k(w[p + "moe.shared." + n]) for n in ("gate", "up", "down")},
            },
        }
        if i in cfg["gqa_layers"]:
            layer["attn"] = {
                "q": k(w[p + "q_proj"].reshape(d, h, hd)),
                "k": k(w[p + "k_proj"].reshape(d, hk, hd)),
                "v": k(w[p + "v_proj"].reshape(d, hk, hd)),
                "gate": k(w[p + "gate_proj"]),
                "o": k(w[p + "o_proj"].reshape(h, hd, d)),
            }
        else:
            q = p + "kda."
            layer["kda"] = {
                **{n: k(w[q + n + "_proj"].reshape(d, lh, ld)) for n in "qkv"},
                **{n + "_conv": w[q + n + "_conv"] for n in "qkv"},
                **{n: k(w[q + n]) for n in ("f_a", "f_b", "g_a", "g_b", "beta")},
                **dict(zip(("A_log", "dt_bias"), decay_leaves(w[q + "A_draw"], w[q + "dt_draw"]))),
                "o_norm": w[q + "o_norm"],
                "o": k(w[q + "o_proj"].reshape(lh, ld, d)),
            }
        tree[f"layer_{i}"] = layer
    return tree
