"""Laguna: a period of one global grouped-query layer and three
sliding-window layers, a number of query heads that is the layer's own, a
per-head output gate, a leading dense layer and then routed experts beside
a shared one. Layers of two kinds and a window, so its own sums.
``num_experts`` is the experts HELD here, ``num_experts_published`` the
router's width.

What the mathematics needs, whatever implements it: a window layer's keys
for a row of n tokens are min(n, window) in bytes (a decode step) and
sum_i min(i, window) query-key pairs (a prefill); a global layer's are n
and n (n + 1) / 2. A program that reads more than the window (a ring wider
than it, a whole masked row) reads under 100% of this roofline."""

from __future__ import annotations

from benchmarks import costs

FAMILY = "laguna"
KINDS = ("full_attention", "sliding_attention")


def width(c: dict) -> int:
    return c.get("num_experts_published", c["num_experts"])


def layers_of(c: dict) -> list:
    """(attention kind, query heads, is dense) of each layer."""
    return [
        (kind, heads, mlp == "dense")
        for kind, heads, mlp in zip(c["layer_types"], c["num_attention_heads_per_layer"], c["mlp_layer_types"])
    ]


def attention_params(c: dict, heads: int) -> int:
    """q, k, v, o and the per-head gate of a layer of ``heads`` query heads."""
    d, hd = c["hidden_size"], c["head_dim"]
    return 2 * d * heads * hd + 2 * d * c["num_key_value_heads"] * hd + d * heads


def layer_params(c: dict) -> dict:
    """Matmul parameters by part (norm scales left out), the two kinds of
    attention layer apart (each at the head count its layers have)."""
    d = c["hidden_size"]
    heads = {kind: h for kind, h, _ in layers_of(c)}
    return {
        **{kind: attention_params(c, h) for kind, h in heads.items()},
        "dense_ffn": 3 * d * c["intermediate_size"],
        "expert": 3 * d * c["moe_intermediate_size"],
        "shared": 3 * d * c["shared_expert_intermediate_size"],
        "router": d * width(c),
        "n_experts": width(c),
        "held": c["num_experts"],
        "top_k": c["num_experts_per_tok"],
        "embed": c["vocab_size"] * d,
        "head": c["vocab_size"] * d,
    }


def _weights(c: dict, experts_per_layer: float) -> float:
    """Parameters of every layer with ``experts_per_layer`` routed experts
    counted in each expert layer, and the head (the embedding apart)."""
    p = layer_params(c)
    moe = experts_per_layer * p["expert"] + p["shared"] + p["router"]
    return sum(
        attention_params(c, heads) + (p["dense_ffn"] if dense else moe) for _, heads, dense in layers_of(c)
    ) + p["head"]


def parameters(c: dict) -> int:
    """Every matmul parameter held here, embedding and head included."""
    return int(_weights(c, c["num_experts"])) + layer_params(c)["embed"]


def active_matmul_params(c: dict) -> int:
    """Parameters one token multiplies with here: of its ``top_k``
    experts the expected share held here."""
    p = layer_params(c)
    return int(_weights(c, p["top_k"] * p["held"] / p["n_experts"]))


def cache_bytes_per_token(c: dict, bytes_per: int = 2) -> int:
    """Keys and values of the K/V heads, over all layers, of a token that
    every layer still holds: a row of n tokens holds this for min(n,
    window) tokens in the window layers (``row_cache_bytes``)."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per * c["num_hidden_layers"]


def row_cache_bytes(c: dict, n: int, bytes_per: int = 2) -> int:
    """Keys and values a row of ``n`` tokens needs read in a decode step:
    all n in the global layers, the last ``sliding_window`` in the others."""
    per_layer = 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per
    return per_layer * sum(
        n if kind == KINDS[0] else min(n, c["sliding_window"]) for kind, _, _ in layers_of(c)
    )


def attended_pairs(c: dict, kind: str, n: int) -> float:
    """Query-key pairs of a causal prefill of ``n`` tokens in one layer:
    n (n + 1) / 2, or sum_i min(i, window) with a window."""
    w = c["sliding_window"]
    if kind == KINDS[0] or n <= w:
        return n * (n + 1) / 2.0
    return w * (w + 1) / 2.0 + (n - w) * float(w)


def prefill_flops(c: dict, prompt_lens) -> float:
    p = layer_params(c)
    body = active_matmul_params(c) - p["head"]
    total = 0.0
    for n in prompt_lens:
        total += 2.0 * body * n + 2.0 * p["head"]
        for kind, heads, _ in layers_of(c):
            total += 2.0 * heads * 2 * c["head_dim"] * attended_pairs(c, kind, n)
    return total


def prefill_chunk_flops(c: dict, tokens: int, prompt_lens) -> float:
    return costs.chunk_share(prefill_flops(c, prompt_lens), layer_params(c)["head"], tokens, prompt_lens)


def decode_step_bytes(c: dict, row_tokens, bytes_per: int = 2) -> float:
    """Every weight read once (of the held experts, those the live rows
    reach in expectation), one row of the embedding per live row, and each
    row's keys and values: all of them in the global layers, a window's
    worth in the others."""
    p, rows = layer_params(c), len(row_tokens)
    touched = costs.expected_experts_touched(p["n_experts"], p["top_k"], rows, p["held"]) if rows else 0.0
    weights = _weights(c, touched) + rows * c["hidden_size"]
    return weights * bytes_per + sum(row_cache_bytes(c, n, bytes_per) for n in row_tokens)
