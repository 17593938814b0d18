"""Olmo-Hybrid: dense; three Gated DeltaNet layers and one multi-head
softmax layer a period, the same MLP behind both; no experts, so a decode
step reads every weight. Layers are of two kinds that hold two kinds of
cache (keys and values by the token in the softmax layers, state by the
row in the linear ones), so its own sums.

The delta rule's own costs (``gdn_*``) are what the ALGORITHM needs,
whatever a program spends. Per token and head, with the carried state S
[d_k, d_v]: S'^T k for the delta, the rank-one write, S^T q for the
output (3 x 2 d_k d_v FLOPs). The chunkwise form does the same three
products against the block's incoming state and adds, inside a block of
C positions, the causal half of K K^T and Q K^T (2 x 2 d_k x (C + 1) / 2
a token), the forward substitution of the unit-triangular system
((C - 1) d_v a token) and the causal half of (Q K^T) W (2 d_v (C + 1) /
2). C is ``BLOCK``: the rule's block of 64 positions, which the config
does not carry. Decay weights, the convolution, norms and gates are
elementwise and left out."""

from __future__ import annotations

from benchmarks import costs

FAMILY = "olmo_hybrid"
#: Positions a block of the chunkwise delta rule (tpufw.ops.kda.BLOCK).
BLOCK = 64


def linear_dims(c: dict):
    """(heads, key channels a head, value channels a head, conv kernel)."""
    return (c["linear_num_key_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"])


def n_layers_of(c: dict, kind: str) -> int:
    return sum(k == kind for k in c["layer_types"])


def layer_params(c: dict) -> dict:
    """Parameters by part: a softmax layer's attention (projections and
    the two whole-width norm scales), a linear layer's mixer (projections,
    three convolutions, A_log, dt_bias, the output norm's scale), the MLP
    and the two norms of either; embedding and head."""
    d = c["hidden_size"]
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    lh, dk, dv, kk = linear_dims(c)
    return {
        "full": 2 * d * h * hd + 2 * d * hk * hd + (h + hk) * hd,
        "linear": d * (2 * lh * dk + 2 * lh * dv + 2 * lh) + lh * dv * d + kk * (2 * lh * dk + lh * dv) + 2 * lh + dv,
        "mlp": 3 * d * c["intermediate_size"],
        "norms": 2 * d,
        "embed": c["vocab_size"] * d,
        "head": c["vocab_size"] * d,
    }


def layer_total(c: dict, kind: str) -> int:
    p = layer_params(c)
    return p["full" if kind == "full_attention" else "linear"] + p["mlp"] + p["norms"]


def parameters(c: dict) -> int:
    """Every parameter held here: the layers, the final norm, the
    embedding and the head."""
    p = layer_params(c)
    return sum(layer_total(c, k) for k in c["layer_types"]) + c["hidden_size"] + p["embed"] + p["head"]


def active_matmul_params(c: dict) -> int:
    """Parameters one token multiplies with: every layer's projections
    and MLP, and the head (the embedding is a lookup; norm scales, the
    convolutions and the decay's constants are elementwise)."""
    d = c["hidden_size"]
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    lh, dk, dv, _ = linear_dims(c)
    p = layer_params(c)
    full = 2 * d * h * hd + 2 * d * hk * hd
    linear = d * (2 * lh * dk + 2 * lh * dv + 2 * lh) + lh * dv * d
    return (n_layers_of(c, "full_attention") * (full + p["mlp"])
            + n_layers_of(c, "linear_attention") * (linear + p["mlp"]) + p["head"])


def cache_bytes_per_token(c: dict, bytes_per: int = 2) -> int:
    """Keys and values of every head, in the softmax layers."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return 2 * c["num_key_value_heads"] * hd * bytes_per * n_layers_of(c, "full_attention")


def state_bytes_per_row(c: dict, bytes_per: int = 2) -> int:
    """What a row keeps in every linear layer whatever its length: per
    head a [d_k, d_v] float32 state, and the three convolutions' last
    kernel - 1 inputs in the activations' type."""
    lh, dk, dv, kk = linear_dims(c)
    a_layer = lh * dk * dv * 4 + (kk - 1) * (2 * lh * dk + lh * dv) * bytes_per
    return a_layer * n_layers_of(c, "linear_attention")


# ------------------------------------------- the delta rule's own costs


def gdn_chunk_flops(c: dict, tokens: int) -> float:
    """FLOPs the chunkwise rule needs for ``tokens`` positions of one
    row, all linear layers."""
    lh, dk, dv, _ = linear_dims(c)
    inside = (BLOCK + 1) / 2.0
    per_token = lh * (3 * 2.0 * dk * dv + 2 * 2.0 * dk * inside + (BLOCK - 1.0) * dv + 2.0 * dv * inside)
    return per_token * tokens * n_layers_of(c, "linear_attention")


def _stream_bytes(c: dict, bytes_per: int) -> int:
    """Bytes a position's q, k, v in and o out take (the activations'
    type) and its g and beta (float32), a layer."""
    lh, dk, dv, _ = linear_dims(c)
    return (2 * lh * dk + 2 * lh * dv) * bytes_per + 2 * lh * 4


def gdn_chunk_bytes(c: dict, tokens: int, bytes_per: int = 2) -> float:
    """Bytes one call of the chunkwise rule has to move for ``tokens``
    positions of one row, all linear layers: the state read and written
    once, each position's inputs and output."""
    lh, dk, dv, _ = linear_dims(c)
    return float((2 * lh * dk * dv * 4 + tokens * _stream_bytes(c, bytes_per)) * n_layers_of(c, "linear_attention"))


def gdn_step_bytes(c: dict, rows: int, bytes_per: int = 2) -> float:
    """Bytes one step of the rule has to move for ``rows`` rows, all
    linear layers: each row's state read and written, its inputs and
    output. Logical bytes: a [96, 192] float32 tile padded to 256 lanes
    moves a third more."""
    lh, dk, dv, _ = linear_dims(c)
    return float(rows * (2 * lh * dk * dv * 4 + _stream_bytes(c, bytes_per)) * n_layers_of(c, "linear_attention"))


# --------------------------------------------------- what the harness asks


def prefill_flops(c: dict, prompt_lens) -> float:
    """2 per active parameter per token, the delta rule's own FLOPs by the
    token, causal attention's scores and values by the pair in the softmax
    layers; the head once per prompt."""
    head = layer_params(c)["head"]
    body = active_matmul_params(c) - head
    hd = c["hidden_size"] // c["num_attention_heads"]
    per_key = c["num_attention_heads"] * 2 * hd
    n_full = n_layers_of(c, "full_attention")
    total = 0.0
    for n in prompt_lens:
        total += 2.0 * body * n + gdn_chunk_flops(c, n) + 2.0 * head
        total += 2.0 * per_key * n_full * n * (n + 1) / 2.0
    return total


def prefill_chunk_flops(c: dict, tokens: int, prompt_lens) -> float:
    return costs.chunk_share(prefill_flops(c, prompt_lens), layer_params(c)["head"], tokens, prompt_lens)


def decode_step_bytes(c: dict, row_tokens, bytes_per: int = 2) -> float:
    """Every weight read once (the embedding: one row per live row), the
    keys and values of each live row's tokens in the softmax layers, and
    each live row's state read and written in the linear ones."""
    rows = len(row_tokens)
    weights = parameters(c) - layer_params(c)["embed"] + rows * c["hidden_size"]
    return (weights * bytes_per + sum(row_tokens) * cache_bytes_per_token(c, bytes_per)
            + rows * 2 * state_bytes_per_row(c, bytes_per))
