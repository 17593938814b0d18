"""Operations and bytes the algorithm needs, as functions of shapes. The
yardstick of every roofline and MFU share; nothing here is measured."""

from __future__ import annotations


def _attn_params_deepseek(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv, kvr = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    return d * h * (dn + dr) + d * (kvr + dr) + kvr * h * (dn + dv) + h * dv * d


def layer_params(family: str, c: dict) -> dict:
    """Matmul parameters of the configuration's parts (norms left out):
    per-layer attention, dense FFN, one routed expert, shared experts,
    router; and embedding and head."""
    d = c["hidden_size"]
    if family == "deepseek_v2":
        f = c["moe_intermediate_size"]
        return {
            "attn": _attn_params_deepseek(c),
            "dense_ffn": 3 * d * c["intermediate_size"],
            "expert": 3 * d * f,
            "shared": 3 * d * f * c["n_shared_experts"],
            "router": d * c["n_routed_experts"],
            "n_experts": c["n_routed_experts"],
            "top_k": c["num_experts_per_tok"],
            "n_dense": c["first_k_dense_replace"],
            "n_moe": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "embed": c["vocab_size"] * d,
            "head": c["vocab_size"] * d,
        }
    if family == "mixtral":
        hd = c["head_dim"]
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
    raise KeyError(f"no cost functions for family {family!r}")


def cache_bytes_per_token(family: str, c: dict, bytes_per: int = 2) -> int:
    """Cache bytes one token holds over all layers."""
    if family == "deepseek_v2":
        per_layer = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    else:
        per_layer = 2 * c["num_key_value_heads"] * c["head_dim"]
    return per_layer * bytes_per * c["num_hidden_layers"]


def active_matmul_params(family: str, c: dict) -> int:
    """Parameters one token multiplies with in a forward pass (the head
    included, the embedding lookup not)."""
    p = layer_params(family, c)
    per_moe = p["attn"] + p["top_k"] * p["expert"] + p["shared"] + p["router"]
    return p["n_dense"] * (p["attn"] + p["dense_ffn"]) + p["n_moe"] * per_moe + p["head"]


def prefill_flops(family: str, c: dict, prompt_lens) -> float:
    """Forward FLOPs of prefilling prompts of these lengths: 2 per active
    parameter per token, plus causal attention scores and values. The head
    runs once per prompt (the last position), so its share is counted so."""
    p = layer_params(family, c)
    body = active_matmul_params(family, c) - p["head"]
    if family == "deepseek_v2":
        per_key = c["num_attention_heads"] * (
            c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
        )
    else:
        per_key = c["num_attention_heads"] * 2 * c["head_dim"]
    total = 0.0
    for n in prompt_lens:
        total += 2.0 * body * n + 2.0 * p["head"]
        total += 2.0 * per_key * c["num_hidden_layers"] * n * (n + 1) / 2.0
    return total


def prefill_chunk_flops(family: str, c: dict, tokens: int, prompt_lens) -> float:
    """Forward FLOPs of prefilling ``tokens`` prompt tokens taken from
    prompts of ``prompt_lens``: 2 per active parameter per token, and the
    attention of each at the mean number of keys a token of those prompts
    attends, sum(n (n + 1) / 2) / sum(n). The head is left out: it is
    needed once per prompt, whatever number of chunks the prompt took."""
    whole = prefill_flops(family, c, prompt_lens) - 2.0 * layer_params(family, c)["head"] * len(prompt_lens)
    return whole * tokens / sum(prompt_lens)


def expected_experts_touched(n_experts: int, top_k: int, rows: int) -> float:
    """Expected number of distinct experts a step of ``rows`` tokens
    reaches under uniform routing."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** rows)


def decode_step_bytes(family: str, c: dict, rows: int, live_tokens: int, bytes_per: int = 2) -> float:
    """Bytes one decode step has to read: the weights of the experts
    routed to (expected under uniform routing over ``rows`` live rows),
    every other weight once (one row of the embedding per live row), and
    the cache of the live tokens."""
    p = layer_params(family, c)
    touched = expected_experts_touched(p["n_experts"], p["top_k"], rows) if rows else 0.0
    per_moe = p["attn"] + touched * p["expert"] + p["shared"] + p["router"]
    weights = p["n_dense"] * (p["attn"] + p["dense_ffn"]) + p["n_moe"] * per_moe + p["head"]
    weights += rows * c["hidden_size"]
    return weights * bytes_per + live_tokens * cache_bytes_per_token(family, c, bytes_per)
