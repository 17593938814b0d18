"""DeepSeek-V2: latent attention (MLA) over a latent cache, a leading
dense layer, then layers of routed experts beside shared ones."""

from __future__ import annotations

from benchmarks import costs

FAMILY = "deepseek_v2"


def _attn_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv, kvr = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    return d * h * (dn + dr) + d * (kvr + dr) + kvr * h * (dn + dv) + h * dv * d


def layer_params(c: dict) -> dict:
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    return {
        "attn": _attn_params(c),
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


def cache_bytes_per_token(c: dict, bytes_per: int = 2) -> int:
    """The compressed latent and the shared rope key, per layer."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * bytes_per * c["num_hidden_layers"]


def active_matmul_params(c: dict) -> int:
    return costs.moe_active_params(layer_params(c))


def prefill_flops(c: dict, prompt_lens) -> float:
    per_key = c["num_attention_heads"] * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    return costs.causal_prefill_flops(layer_params(c), per_key, c["num_hidden_layers"], prompt_lens)


def prefill_chunk_flops(c: dict, tokens: int, prompt_lens) -> float:
    return costs.chunk_share(prefill_flops(c, prompt_lens), layer_params(c)["head"], tokens, prompt_lens)


def decode_step_bytes(c: dict, row_tokens, bytes_per: int = 2) -> float:
    weights = costs.moe_decode_weight_bytes(layer_params(c), c["hidden_size"], len(row_tokens), bytes_per)
    return weights + sum(row_tokens) * cache_bytes_per_token(c, bytes_per)
