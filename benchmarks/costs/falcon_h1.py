"""Falcon-H1: every layer a Mamba-2 mixer and grouped-query attention on
one input, then a dense SwiGLU MLP; no experts, so a decode step reads
every weight. Layers are alike but hold two kinds of cache (keys and
values by the token, state by the row), so its own sums.

The state-space recurrence's own costs (``ssd_*``) are what the
ALGORITHM needs, whatever a program spends: per token and head the read
of the carried [P, N] state for the output and its update (2 x 2 P N
FLOPs), and inside a block of C positions the causal half of the two
[C, C] products (C.B a group, and with x a head: (C + 1) / 2 positions a
token on average). The decay weights, the convolution and the gate are
elementwise and left out."""

from __future__ import annotations

from benchmarks import costs

FAMILY = "falcon_h1"


def ssm_dims(c: dict):
    """(heads, channels a head, state size, groups, conv kernel, block)."""
    return (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"], c["mamba_n_groups"],
            c["mamba_d_conv"], c["mamba_chunk_size"])


def layer_params(c: dict) -> dict:
    """Parameters by part: a layer's attention, mixer (projections,
    convolution and its bias, A_log, dt_bias, D, the grouped norm's
    scale), MLP and two norms; embedding and head."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, p, n, g, k, _ = ssm_dims(c)
    inner, conv = h * p, h * p + 2 * g * n
    return {
        "attn": 2 * d * c["num_attention_heads"] * hd + 2 * d * c["num_key_value_heads"] * hd,
        "ssm": d * (inner + conv + h) + inner * d + (k + 1) * conv + 3 * h + inner,
        "mlp": 3 * d * c["intermediate_size"],
        "norms": 2 * d,
        "embed": c["vocab_size"] * d,
        "head": c["vocab_size"] * d,
    }


def layer_total(c: dict) -> int:
    p = layer_params(c)
    return p["attn"] + p["ssm"] + p["mlp"] + p["norms"]


def parameters(c: dict) -> int:
    """Every parameter held here: the layers, the final norm, the
    embedding and the head."""
    p = layer_params(c)
    return c["num_hidden_layers"] * layer_total(c) + c["hidden_size"] + p["embed"] + p["head"]


def active_matmul_params(c: dict) -> int:
    """Parameters one token multiplies with: the three parts of every
    layer and the head (the embedding is a lookup)."""
    p = layer_params(c)
    return c["num_hidden_layers"] * (p["attn"] + p["ssm"] + p["mlp"]) + p["head"]


def cache_bytes_per_token(c: dict, bytes_per: int = 2) -> int:
    """Keys and values of the K/V heads, in every layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per * c["num_hidden_layers"]


def state_bytes_per_row(c: dict, bytes_per: int = 2) -> int:
    """What a row keeps in every layer whatever its length: per head a
    [P, N] float32 state, and the convolution's last kernel - 1 inputs in
    the activations' type."""
    h, p, n, g, k, _ = ssm_dims(c)
    return (h * p * n * 4 + (k - 1) * (h * p + 2 * g * n) * bytes_per) * c["num_hidden_layers"]


# ------------------------------------------- the recurrence's own costs


def ssd_chunk_flops(c: dict, tokens: int) -> float:
    """FLOPs the chunkwise recurrence needs for ``tokens`` positions of
    one row, all layers."""
    h, p, n, g, _, block = ssm_dims(c)
    inside = (block + 1) / 2.0
    per_token = h * (2.0 * 2 * p * n + 2.0 * p * inside) + g * 2.0 * n * inside
    return per_token * tokens * c["num_hidden_layers"]


def _stream_bytes(c: dict, bytes_per: int) -> int:
    """Bytes a position's x, B, C, dt in and y out take, a layer."""
    h, p, n, g, _, _ = ssm_dims(c)
    return (2 * h * p + 2 * g * n + h) * bytes_per


def ssd_chunk_bytes(c: dict, tokens: int, bytes_per: int = 2) -> float:
    """Bytes one call of the chunkwise recurrence has to move for
    ``tokens`` positions of one row, all layers: the state read and
    written once, each position's inputs and output."""
    h, p, n, _, _, _ = ssm_dims(c)
    return float((2 * h * p * n * 4 + tokens * _stream_bytes(c, bytes_per)) * c["num_hidden_layers"])


def ssd_step_bytes(c: dict, rows: int, bytes_per: int = 2) -> float:
    """Bytes one step of the recurrence has to move for ``rows`` rows,
    all layers: each row's state read and written, its inputs and output."""
    h, p, n, _, _, _ = ssm_dims(c)
    return float(rows * (2 * h * p * n * 4 + _stream_bytes(c, bytes_per)) * c["num_hidden_layers"])


# --------------------------------------------------- what the harness asks


def prefill_flops(c: dict, prompt_lens) -> float:
    """2 per active parameter per token, the recurrence's own FLOPs by the
    token, causal attention's scores and values by the pair in every
    layer; the head once per prompt."""
    head = layer_params(c)["head"]
    body = active_matmul_params(c) - head
    per_key = c["num_attention_heads"] * 2 * c["head_dim"]
    total = 0.0
    for n in prompt_lens:
        total += 2.0 * body * n + ssd_chunk_flops(c, n) + 2.0 * head
        total += 2.0 * per_key * c["num_hidden_layers"] * n * (n + 1) / 2.0
    return total


def prefill_chunk_flops(c: dict, tokens: int, prompt_lens) -> float:
    return costs.chunk_share(prefill_flops(c, prompt_lens), layer_params(c)["head"], tokens, prompt_lens)


def decode_step_bytes(c: dict, row_tokens, bytes_per: int = 2) -> float:
    """Every weight read once (the embedding: one row per live row), the
    keys and values of each live row's tokens in every layer, and each
    live row's state read and written."""
    rows = len(row_tokens)
    weights = parameters(c) - layer_params(c)["embed"] + rows * c["hidden_size"]
    return (weights * bytes_per + sum(row_tokens) * cache_bytes_per_token(c, bytes_per)
            + rows * 2 * state_bytes_per_row(c, bytes_per))
