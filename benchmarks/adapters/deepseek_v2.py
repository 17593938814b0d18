"""DeepSeek-V2 through the program: the program's configuration built from
the published keys, and the reference-named weights re-labelled as the
program's parameter tree. The one place that knows both namings."""

from __future__ import annotations

import jax.numpy as jnp

FAMILY = "deepseek_v2"


def program_model(cfg: dict, assumed: dict):
    """(model class, program configuration) for the published ``cfg``."""
    from tpufw.models.deepseek import Deepseek, DeepseekConfig, YarnScaling

    if cfg["topk_method"] != "greedy" or cfg["scoring_func"] != "softmax":
        raise ValueError("the program routes greedy softmax top-k only")
    rs = cfg.get("rope_scaling")
    yarn = None
    if rs:
        yarn = YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]),
        )
    pc = DeepseekConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=yarn,
        max_seq_len=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        moe_dispatch=assumed["moe_dispatch"],
        remat=False,
        scan_layers=False,
        tie_embeddings=cfg["tie_word_embeddings"],
        n_routed_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense=cfg["first_k_dense_replace"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        # Dropless, as the published model is at inference.
        capacity_factor=cfg["n_routed_experts"] / cfg["num_experts_per_tok"],
    )
    return Deepseek, pc


def to_program(w: dict, cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    d, kvr = cfg["hidden_size"], cfg["kv_lora_rank"]
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
            "attn": {
                "q": k(w[p + "q_proj"].reshape(d, h, dn + dr)),
                "kv_a": k(w[p + "kv_a_proj"]),
                "kv_a_norm": {"scale": w[p + "kv_a_norm"]},
                "kv_b_kernel": w[p + "kv_b_proj"].reshape(kvr, h, dn + dv),
                "o": k(w[p + "o_proj"].reshape(h, dv, d)),
            },
        }
        if i < cfg["first_k_dense_replace"]:
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

