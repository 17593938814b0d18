"""Phi-4-mini-flash through the program: the program's configuration
built from the published keys, and the reference-named weights re-labelled
as the program's parameter tree: a (Mamba, window) or (GMU, cross) pair of
layers to a ``self_layer_{p}`` / ``cross_layer_{p}``, the two layers
between them ``memory`` and ``full``."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference.phi4flash import D_CONV, D_STATE, EXPAND, a_log, dt_bias, head_dim, layer_kinds
# At import, not inside ``program_model``: a program that lacks the family
# fails the serve phase before it makes eight gigabytes of weights.
from tpufw.models.phi4flash import Phi4Flash, Phi4FlashConfig

FAMILY = "phi4flash"


def program_model(cfg: dict, assumed: dict):
    pc = Phi4FlashConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=head_dim(cfg),
        d_ff=cfg["intermediate_size"],
        rms_eps=cfg["layer_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        tie_embeddings=cfg["tie_word_embeddings"],
        sliding_window=cfg["sliding_window"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        remat=False,
        # As the server unrolls any trunk it is handed
        # (serve._maybe_unroll): a pair of layers to a ``*_layer_{p}``.
        scan_layers=False,
        mamba_state=D_STATE,
        mamba_conv=D_CONV,
        mamba_expand=EXPAND,
    )
    layer_kinds(cfg)  # raises on a depth or an mb_per_layer it does not cover
    return Phi4Flash, pc


def block_tree(w: dict, i: int, cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    k = lambda x: {"kernel": x}
    p, kind = f"layers.{i}.", layer_kinds(cfg)[i]
    norm = lambda n: {"scale": w[p + n + ".scale"], "bias": w[p + n + ".bias"]}
    tree = {
        "mixer_norm": norm("norm1"),
        "mlp_norm": norm("norm2"),
        "mlp": {"gate": k(w[p + "mlp.w1"][:, :f]), "up": k(w[p + "mlp.w1"][:, f:]), "down": k(w[p + "mlp.w2"])},
    }
    if kind in ("mamba", "memory"):
        q = p + "mamba."
        tree["mamba"] = {
            "in_proj": k(w[q + "in_proj"]),
            "conv": w[q + "conv"],
            "conv_bias": w[q + "conv_bias"],
            "x_proj": k(w[q + "x_proj"]),
            "dt_proj": {"kernel": w[q + "dt_proj"], "bias": dt_bias(w[q + "dt_draw"])},
            # The program keeps the state [d_state, d_inner], channels on
            # the lanes, and A_log beside it.
            "A_log": a_log(cfg).T,
            "D": w[q + "D"],
            "out_proj": k(w[q + "out_proj"]),
        }
    elif kind == "gmu":
        tree["gmu"] = {n: k(w[p + "gmu." + n]) for n in ("in_proj", "out_proj")}
    else:
        q = p + "attn."
        qkv, bias = w[q + "qkv"], w[q + "qkv_bias"]
        cuts = {"q": (0, h), "k": (h, h + hk), "v": (h + hk, h + 2 * hk)}
        tree["attn"] = {
            name: {"kernel": qkv[:, a * hd:b * hd].reshape(d, b - a, hd), "bias": bias[a * hd:b * hd].reshape(b - a, hd)}
            for name, (a, b) in cuts.items() if name == "q" or kind != "cross"
        }
        tree["attn"].update({
            "o": {"kernel": w[q + "o"].reshape(h // 2, 2 * hd, d), "bias": w[q + "o_bias"]},
            "subln": w[q + "subln"],
            **{f"lambda_{a}": w[q + f"lambda_{a}"] for a in ("q1", "k1", "q2", "k2")},
        })
    return tree


def to_program(w: dict, cfg: dict) -> dict:
    """The unrolled tree (module docstring)."""
    kinds = layer_kinds(cfg)
    half = len(kinds) // 2
    tree = {
        "embed": {"embedding": w["embed"]},
        "final_norm": {"scale": w["final_norm.scale"], "bias": w["final_norm.bias"]},
        "memory": block_tree(w, half, cfg),
        "full": block_tree(w, half + 1, cfg),
    }
    for side, start, stop in (("self", 0, half), ("cross", half + 2, len(kinds))):
        for i in range(start, stop, 2):
            tree[f"{side}_layer_{(i - start) // 2}"] = {kinds[j]: block_tree(w, j, cfg) for j in (i, i + 1)}
    return tree
