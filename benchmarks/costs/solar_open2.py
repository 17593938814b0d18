"""Solar-Open2: a period of one softmax (grouped-query, gated) layer and
three linear-attention layers, each followed by routed experts beside a
shared one. Layers of two kinds, so its own sums. ``n_routed_experts`` is
the experts HELD here, ``n_routed_experts_published`` the router's width.

Per token and head a linear-attention layer's chunkwise delta rule
(blocks of 64 positions) is counted as 2 x 3 x d_k x d_v FLOPs for the
three products with the carried state (its read for the block's deltas,
its read for the outputs, its update) plus 3 x 2 x 64 x d_k for the rows
of the block's two decay-weighted [64, 64] matrices and of the outputs'
within-block part; the unit-triangular solve (about 64 x d_v / 2
multiply-adds a row) and the elementwise decay weights are left out."""

from __future__ import annotations

from benchmarks import costs

FAMILY = "solar_open2"
#: Positions per block of the chunkwise delta rule (tpufw.ops.kda.BLOCK).
KDA_BLOCK = 64


def width(c: dict) -> int:
    return c.get("n_routed_experts_published", c["n_routed_experts"])


def n_layers_of(c: dict) -> dict:
    n = c["num_hidden_layers"]
    gqa = sum(1 for i in range(n) if i in c["gqa_layers"])
    return {"gqa": gqa, "kda": n - gqa}


def layer_params(c: dict) -> dict:
    """Matmul parameters by part (norm scales, A_log, dt_bias and the
    selection bias left out), the two kinds of mixer apart."""
    d, hd = c["hidden_size"], c["head_dim"]
    la = c["linear_attn_config"]
    lc, rank = la["num_heads"] * la["head_dim"], la["head_dim"]
    f = c["moe_intermediate_size"]
    return {
        "gqa": 3 * d * c["num_attention_heads"] * hd + 2 * d * c["num_key_value_heads"] * hd,
        "kda": 4 * d * lc + 2 * (d * rank + rank * lc) + d * la["num_heads"]
               + 3 * la["short_conv_kernel_size"] * lc,
        "expert": 3 * d * f,
        "shared": 3 * d * f * c["n_shared_experts"],
        "router": d * width(c),
        "n_experts": width(c),
        "held": c["n_routed_experts"],
        "top_k": c["num_experts_per_tok"],
        "embed": c["vocab_size"] * d,
        "head": c["vocab_size"] * d,
    }


def parameters(c: dict) -> int:
    """Every matmul parameter held here, embedding and head included."""
    p, n = layer_params(c), n_layers_of(c)
    moe = p["held"] * p["expert"] + p["shared"] + p["router"]
    return n["gqa"] * p["gqa"] + n["kda"] * p["kda"] + (n["gqa"] + n["kda"]) * moe + p["embed"] + p["head"]


def cache_bytes_per_token(c: dict, bytes_per: int = 2) -> int:
    """Keys and values of the K/V heads, in the softmax layers only."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per * n_layers_of(c)["gqa"]


def state_bytes_per_row(c: dict, bytes_per: int = 2) -> int:
    """What a row keeps in the linear-attention layers whatever its
    length: per head a [d_k, d_v] float32 state, and the last kernel - 1
    inputs of the three convolutions in the activations' type."""
    la = c["linear_attn_config"]
    h, d = la["num_heads"], la["head_dim"]
    per_layer = h * d * d * 4 + (la["short_conv_kernel_size"] - 1) * 3 * h * d * bytes_per
    return per_layer * n_layers_of(c)["kda"]


def active_matmul_params(c: dict) -> int:
    """Parameters one token multiplies with here: of its ``top_k``
    experts the expected share held here."""
    p, n = layer_params(c), n_layers_of(c)
    routed = p["top_k"] * p["expert"] * p["held"] // p["n_experts"]
    moe = routed + p["shared"] + p["router"]
    return n["gqa"] * p["gqa"] + n["kda"] * p["kda"] + (n["gqa"] + n["kda"]) * moe + p["head"]


def kda_flops_per_token(c: dict) -> float:
    la = c["linear_attn_config"]
    h, d = la["num_heads"], la["head_dim"]
    return h * (2.0 * 3 * d * d + 3 * 2.0 * KDA_BLOCK * d) * n_layers_of(c)["kda"]


def prefill_flops(c: dict, prompt_lens) -> float:
    p = layer_params(c)
    body = active_matmul_params(c) - p["head"]
    per_key = c["num_attention_heads"] * 2 * c["head_dim"]
    total = 0.0
    for n in prompt_lens:
        total += (2.0 * body + kda_flops_per_token(c)) * n + 2.0 * p["head"]
        total += 2.0 * per_key * n_layers_of(c)["gqa"] * n * (n + 1) / 2.0
    return total


def prefill_chunk_flops(c: dict, tokens: int, prompt_lens) -> float:
    return costs.chunk_share(prefill_flops(c, prompt_lens), layer_params(c)["head"], tokens, prompt_lens)


def decode_step_bytes(c: dict, row_tokens, bytes_per: int = 2) -> float:
    """Every weight read once (of the held experts, those the live rows
    reach in expectation), one row of the embedding per live row, the
    keys and values of each row's tokens in the softmax layers, and each
    row's state read and written."""
    p, n, rows = layer_params(c), n_layers_of(c), len(row_tokens)
    touched = costs.expected_experts_touched(p["n_experts"], p["top_k"], rows, p["held"]) if rows else 0.0
    moe = touched * p["expert"] + p["shared"] + p["router"]
    weights = n["gqa"] * p["gqa"] + n["kda"] * p["kda"] + (n["gqa"] + n["kda"]) * moe + p["head"]
    weights += rows * c["hidden_size"]
    return (weights * bytes_per + sum(row_tokens) * cache_bytes_per_token(c, bytes_per)
            + rows * 2 * state_bytes_per_row(c, bytes_per))
