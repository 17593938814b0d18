"""Olmo-Hybrid through the program: the program's configuration built
from the published keys, and the reference-named weights re-labelled as
the program's parameter tree, a period of layers to a ``layer_{p}``."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference.olmo_hybrid import decay_leaves, head_dim, layer_kinds, linear_dims
# At import, not inside ``program_model``: a program that lacks the family
# fails the serve phase before it makes eight gigabytes of weights.
from tpufw.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig

FAMILY = "olmo_hybrid"


def program_model(cfg: dict, assumed: dict):
    if cfg["rope_parameters"]["rope_theta"] is not None or cfg["attention_bias"]:
        raise ValueError("this adapter covers full layers with no rotary embedding and no bias")
    lh, dk, dv, kk = linear_dims(cfg)
    pc = OlmoHybridConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=head_dim(cfg),
        d_ff=cfg["intermediate_size"],
        rms_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        remat=False,
        # As the server unrolls any trunk it is handed
        # (serve._maybe_unroll): ``layer_{p}`` a period.
        scan_layers=False,
        layer_types=tuple(layer_kinds(cfg)),
        gdn_heads=lh,
        gdn_key_dim=dk,
        gdn_value_dim=dv,
        gdn_conv=kk,
        gdn_neg_eigval=cfg["linear_allow_neg_eigval"],
    )
    pc.check_layers()
    return OlmoHybrid, pc


def block_tree(w: dict, i: int, cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    lh, dk, dv, _ = linear_dims(cfg)
    k = lambda x: {"kernel": x}
    p = f"layers.{i}."
    tree = {
        "mixer_norm": {"scale": w[p + "mixer_norm"]},
        "mlp_norm": {"scale": w[p + "mlp_norm"]},
        "mlp": {n: k(w[p + "mlp." + n]) for n in ("gate", "up", "down")},
    }
    if layer_kinds(cfg)[i] == "full_attention":
        tree["attn"] = {
            "q": k(w[p + "q_proj"].reshape(d, h, hd)),
            "k": k(w[p + "k_proj"].reshape(d, hk, hd)),
            "v": k(w[p + "v_proj"].reshape(d, hk, hd)),
            "o": k(w[p + "o_proj"].reshape(h, hd, d)),
            "q_norm": {"scale": w[p + "q_norm"]},
            "k_norm": {"scale": w[p + "k_norm"]},
        }
        return tree
    q = p + "gdn."
    a_log, dt_bias = decay_leaves(w[q + "A_draw"], w[q + "dt_draw"])
    tree["gdn"] = {
        "q": k(w[q + "q"].reshape(d, lh, dk)),
        "k": k(w[q + "k"].reshape(d, lh, dk)),
        "v": k(w[q + "v"].reshape(d, lh, dv)),
        "o": k(w[q + "o"].reshape(lh, dv, d)),
        "gate": k(w[q + "gate"]),
        "decay": k(w[q + "decay"]),
        "beta": k(w[q + "beta"]),
        "q_conv": w[q + "q_conv"],
        "k_conv": w[q + "k_conv"],
        "v_conv": w[q + "v_conv"],
        "A_log": a_log,
        "dt_bias": dt_bias,
        "o_norm": w[q + "o_norm"],
    }
    return tree


def to_program(w: dict, cfg: dict) -> dict:
    """The unrolled tree: ``layer_{p}`` a period, its blocks named by
    kind and place (``linear_0`` .. ``full_3``)."""
    kinds = layer_kinds(cfg)
    span = kinds.index("full_attention") + 1
    tree = {
        "embed": {"embedding": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"kernel": w["lm_head"]},
    }
    for start in range(0, len(kinds), span):
        tree[f"layer_{start // span}"] = {
            f"{kinds[i].split('_')[0]}_{i - start}": block_tree(w, i, cfg) for i in range(start, start + span)
        }
    return tree
