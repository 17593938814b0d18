"""Phi-4-mini-flash: dense; layers of six kinds that hold three kinds of
cache and one kind that is READ eight times: Mamba-1 state by the row in
the self-decoder's even layers, a ring of the window's keys by the row in
its odd ones, keys and values by the token in ONE full-attention layer,
which the seven cross-attention layers after it read too; the Gated
Memory Units hold nothing. No experts, so a decode step reads every
weight. Its own sums.

What the ALGORITHM needs, whatever a program spends: a key and a value
are counted at the model's widths (20 heads of 64: 5,120 B a token), not
at what a store's tiles pad them to. The selective scan's own costs
(``mamba_*``): per token and channel the decay times the state, the
input's outer product, their sum and the contraction with C over
``D_STATE`` states (6 FLOPs a state; the exponential, the convolution,
norms and gates are elementwise and left out)."""

from __future__ import annotations

from benchmarks import costs

FAMILY = "phi4flash"
#: The Mamba-1 mixer's sizes the config does not carry
#: (benchmarks/reference/phi4flash.py, which imports jax and this does not).
D_STATE, D_CONV, EXPAND = 16, 4, 2
KINDS = ("mamba", "window", "memory", "full", "gmu", "cross")


def mamba_dims(c: dict):
    """(d_inner, d_state, d_conv, dt_rank)."""
    d = c["hidden_size"]
    return EXPAND * d, D_STATE, D_CONV, -(-d // 16)


def layer_kinds(c: dict) -> list:
    n = c["num_hidden_layers"]
    half = n // 2
    return [
        ("mamba" if i % 2 == 0 else "window") if i < half else "memory" if i == half
        else "full" if i == half + 1 else "gmu" if i % 2 == 0 else "cross"
        for i in range(n)
    ]


def n_layers_of(c: dict, *kinds: str) -> int:
    return sum(k in kinds for k in layer_kinds(c))


def readers(c: dict) -> int:
    """Layers that read the one page pair: the full layer and every cross
    layer."""
    return n_layers_of(c, "full", "cross")


def layer_params(c: dict) -> dict:
    """Parameters by part, biases and small vectors included: a Mamba
    mixer, a self-attention mixer (window or full), a Gated Memory Unit, a
    cross-attention mixer, the MLP and the two LayerNorms of any layer;
    the embedding, which is the head too."""
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    inner, n, kk, rank = mamba_dims(c)
    diff = 4 * hd + 2 * hd  # the four lam vectors, the sub-layer norm's scale
    return {
        "mamba": (d * 2 * inner + kk * inner + inner + inner * (rank + 2 * n)
                  + rank * inner + inner + inner * n + inner + inner * d),
        "attn": d * (q + 2 * kv) + (q + 2 * kv) + q * d + d + diff,
        "gmu": 2 * d * inner,
        "cross": d * q + q + q * d + d + diff,
        "mlp": 3 * d * c["intermediate_size"],
        "norms": 4 * d,
        "embed": c["vocab_size"] * d,
    }


def _mixer(kind: str) -> str:
    return {"memory": "mamba", "window": "attn", "full": "attn"}.get(kind, kind)


def layer_total(c: dict, kind: str) -> int:
    p = layer_params(c)
    return p[_mixer(kind)] + p["mlp"] + p["norms"]


def parameters(c: dict) -> int:
    """Every parameter held here: the layers, the final LayerNorm and the
    embedding, once (the head is tied to it)."""
    return sum(layer_total(c, k) for k in layer_kinds(c)) + 2 * c["hidden_size"] + layer_params(c)["embed"]


def active_matmul_params(c: dict) -> int:
    """Parameters one token multiplies with: every layer's projections
    and MLP, and the head (the embedding lookup is a gather, the head its
    matmul; biases, norms, the convolution, A and D are elementwise)."""
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    inner, n, _, rank = mamba_dims(c)
    mix = {
        "mamba": d * 2 * inner + inner * (rank + 2 * n) + rank * inner + inner * d,
        "attn": d * (q + 2 * kv) + q * d,
        "gmu": 2 * d * inner,
        "cross": 2 * d * q,
    }
    p = layer_params(c)
    return sum(mix[_mixer(k)] + p["mlp"] for k in layer_kinds(c)) + p["embed"]


def cache_bytes_per_token(c: dict, bytes_per: int = 2) -> int:
    """Keys and values of every K/V head in the ONE layer that stores
    them by the token."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return 2 * c["num_key_value_heads"] * hd * bytes_per


def state_bytes_per_row(c: dict, bytes_per: int = 2) -> int:
    """What a row keeps in every Mamba layer whatever its length: a
    [d_inner, d_state] float32 state and the convolution's last d_conv - 1
    inputs in the activations' type."""
    inner, n, kk, _ = mamba_dims(c)
    return (inner * n * 4 + (kk - 1) * inner * bytes_per) * n_layers_of(c, "mamba", "memory")


def ring_bytes_per_row(c: dict, tokens: int | None = None, bytes_per: int = 2) -> int:
    """Keys and values of the last ``sliding_window`` tokens (of
    ``tokens``, where the row is shorter) in every window layer."""
    w = c["sliding_window"] if tokens is None else min(tokens, c["sliding_window"])
    return w * cache_bytes_per_token(c, bytes_per) * n_layers_of(c, "window")


# ------------------------------------------- the selective scan's own costs


def mamba_chunk_flops(c: dict, tokens: int) -> float:
    """FLOPs the scan needs for ``tokens`` positions of one row, all
    Mamba layers."""
    inner, n, _, _ = mamba_dims(c)
    return 6.0 * inner * n * tokens * n_layers_of(c, "mamba", "memory")


def _stream_bytes(c: dict, bytes_per: int) -> int:
    """Bytes a position's a in (the activations' type), dt in and y out
    (float32), B and C in, a layer."""
    inner, n, _, _ = mamba_dims(c)
    return inner * bytes_per + 2 * inner * 4 + 2 * n * bytes_per


def mamba_chunk_bytes(c: dict, tokens: int, bytes_per: int = 2) -> float:
    """Bytes one call of the scan has to move for ``tokens`` positions of
    one row, all Mamba layers: the state read and written once, each
    position's inputs and output."""
    inner, n, _, _ = mamba_dims(c)
    return float((2 * inner * n * 4 + tokens * _stream_bytes(c, bytes_per)) * n_layers_of(c, "mamba", "memory"))


def mamba_step_bytes(c: dict, rows: int, bytes_per: int = 2) -> float:
    """Bytes one step of the scan has to move for ``rows`` rows, all
    Mamba layers: each row's state read and written, its inputs and
    output."""
    inner, n, _, _ = mamba_dims(c)
    return float(rows * (2 * inner * n * 4 + _stream_bytes(c, bytes_per)) * n_layers_of(c, "mamba", "memory"))


# --------------------------------------------------- what the harness asks


def attended_pairs(c: dict, kind: str, n: int) -> float:
    """Query-key pairs of a causal prefill of ``n`` tokens in one
    attention layer: n (n + 1) / 2, or sum_i min(i, window) in a window
    layer."""
    w = c["sliding_window"]
    if kind != "window" or n <= w:
        return n * (n + 1) / 2.0
    return w * (w + 1) / 2.0 + (n - w) * float(w)


def prefill_flops(c: dict, prompt_lens) -> float:
    """2 per active parameter per token, the scan's own FLOPs by the
    token, differential attention's scores and values by the pair (each
    query head a key of head_dim and a value of 2 head_dim); the head
    once per prompt. All layers over every position, as the program runs
    them (the cross-decoder needs a prompt's last position only: ROADMAP
    R11's twin)."""
    head = layer_params(c)["embed"]
    body = active_matmul_params(c) - head
    hd = c["hidden_size"] // c["num_attention_heads"]
    per_pair = c["num_attention_heads"] * 2.0 * (hd + 2 * hd)
    kinds = [k for k in layer_kinds(c) if k in ("window", "full", "cross")]
    total = 0.0
    for n in prompt_lens:
        total += 2.0 * body * n + mamba_chunk_flops(c, n) + 2.0 * head
        total += per_pair * sum(attended_pairs(c, k, n) for k in kinds)
    return total


def prefill_chunk_flops(c: dict, tokens: int, prompt_lens) -> float:
    return costs.chunk_share(prefill_flops(c, prompt_lens), layer_params(c)["embed"], tokens, prompt_lens)


def arena_read_bytes(c: dict, row_tokens, bytes_per: int = 2) -> float:
    """Bytes of the one page pair a decode step has to read: each live
    row's keys and values, once for EACH of the layers that attend them."""
    return float(readers(c) * sum(row_tokens) * cache_bytes_per_token(c, bytes_per))


def decode_step_bytes(c: dict, row_tokens, bytes_per: int = 2) -> float:
    """Every weight read once (the tied embedding as the head, and one row
    of it per live row for the lookup), the one arena's keys and values of
    each live row's tokens once a READER (eight at the published depth),
    each live row's rings, and each live row's state read and written."""
    rows = len(row_tokens)
    weights = parameters(c) + rows * c["hidden_size"]
    return (weights * bytes_per + arena_read_bytes(c, row_tokens, bytes_per)
            + sum(ring_bytes_per_row(c, n, bytes_per) for n in row_tokens)
            + rows * 2 * state_bytes_per_row(c, bytes_per))
