"""Falcon-H1 through the program: the program's configuration built from
the published keys, and the reference-named weights re-labelled as the
program's parameter tree."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference.falcon_h1 import decay_leaves, ssm_dims
# At import, not inside ``program_model``: a program that lacks the family
# fails the serve phase before it makes ten gigabytes of weights.
from tpufw.models.falcon_h1 import FalconH1, FalconH1Config

FAMILY = "falcon_h1"


def program_model(cfg: dict, assumed: dict):
    if cfg["rope_scaling"] is not None or cfg["attn_layer_indices"] is not None or not cfg["mamba_use_mlp"]:
        raise ValueError("this adapter covers plain rotary configs with attention and an MLP in every layer")
    sh, sp, sn, sg, kk = ssm_dims(cfg)
    pc = FalconH1Config(
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
        # As the server unrolls any trunk it is handed (serve._maybe_unroll).
        scan_layers=False,
        ssm_heads=sh,
        ssm_head_dim=sp,
        ssm_state=sn,
        ssm_groups=sg,
        ssm_conv=kk,
        ssm_chunk=cfg["mamba_chunk_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        lm_head_multiplier=float(cfg["lm_head_multiplier"]),
        key_multiplier=float(cfg["key_multiplier"]),
        attention_in_multiplier=float(cfg["attention_in_multiplier"]),
        attention_out_multiplier=float(cfg["attention_out_multiplier"]),
        ssm_in_multiplier=float(cfg["ssm_in_multiplier"]),
        ssm_out_multiplier=float(cfg["ssm_out_multiplier"]),
        mlp_multipliers=tuple(float(m) for m in cfg["mlp_multipliers"]),
        ssm_multipliers=tuple(float(m) for m in cfg["ssm_multipliers"]),
    )
    return FalconH1, pc


def to_program(w: dict, cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    k = lambda x: {"kernel": x}
    tree = {
        "embed": {"embedding": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": k(w["lm_head"]),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        q = p + "ssm."
        a_log, dt_bias = decay_leaves(w[q + "A_draw"], w[q + "dt_draw"])
        tree[f"layer_{i}"] = {
            "attn_norm": {"scale": w[p + "attn_norm"]},
            "mlp_norm": {"scale": w[p + "mlp_norm"]},
            "attn": {
                "q": k(w[p + "q_proj"].reshape(d, h, hd)),
                "k": k(w[p + "k_proj"].reshape(d, hk, hd)),
                "v": k(w[p + "v_proj"].reshape(d, hk, hd)),
                "o": k(w[p + "o_proj"].reshape(h, hd, d)),
            },
            "mlp": {n: k(w[p + "mlp." + n]) for n in ("gate", "up", "down")},
            "ssm": {
                # W_in's column groups in the order the config's split takes them.
                "in_proj": k(jnp.concatenate([w[q + "in_" + n] for n in ("z", "x", "B", "C", "dt")], axis=1)),
                "conv": w[q + "conv"],
                "conv_bias": w[q + "conv_bias"],
                "A_log": a_log,
                "dt_bias": dt_bias,
                "D": w[q + "D"],
                "norm": w[q + "norm"],
                "out_proj": k(w[q + "out_proj"]),
            },
        }
    return tree
