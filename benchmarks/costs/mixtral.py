"""Mixtral: grouped-query attention over a KV cache, every layer a layer
of routed experts."""

from __future__ import annotations

from benchmarks import costs

FAMILY = "mixtral"


def layer_params(c: dict) -> dict:
    d, hd = c["hidden_size"], c["head_dim"]
    return {
        "attn": 2 * d * c["num_attention_heads"] * hd + 2 * d * c["num_key_value_heads"] * hd,
        "dense_ffn": 0,
        "expert": 3 * d * c["intermediate_size"],
        "shared": 0,
        "router": d * c["num_local_experts"],
        "n_experts": c["num_local_experts"],
        "top_k": c["num_experts_per_tok"],
        "n_dense": 0,
        "n_moe": c["num_hidden_layers"],
        "embed": c["vocab_size"] * d,
        "head": c["vocab_size"] * d,
    }


def cache_bytes_per_token(c: dict, bytes_per: int = 2) -> int:
    """Keys and values of the KV heads, per layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per * c["num_hidden_layers"]


def active_matmul_params(c: dict) -> int:
    return costs.moe_active_params(layer_params(c))


def prefill_flops(c: dict, prompt_lens) -> float:
    per_key = c["num_attention_heads"] * 2 * c["head_dim"]
    return costs.causal_prefill_flops(layer_params(c), per_key, c["num_hidden_layers"], prompt_lens)


def prefill_chunk_flops(c: dict, tokens: int, prompt_lens) -> float:
    return costs.chunk_share(prefill_flops(c, prompt_lens), layer_params(c)["head"], tokens, prompt_lens)


def decode_step_bytes(c: dict, row_tokens, bytes_per: int = 2) -> float:
    weights = costs.moe_decode_weight_bytes(layer_params(c), c["hidden_size"], len(row_tokens), bytes_per)
    return weights + sum(row_tokens) * cache_bytes_per_token(c, bytes_per)
