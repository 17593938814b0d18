"""Operations and bytes the algorithm needs, as functions of shapes. The
yardstick of every roofline and MFU share; nothing here is measured.

What a family's forward pass costs is the family's to say: the functions
below hand over to ``benchmarks/costs/<family>.py``, found by name as the
reference and the adapter are. That module defines ``REQUIRED``, each a
function of the configuration's published keys ``c``:

- ``decode_step_bytes(c, row_tokens, bytes_per=2)``: bytes one decode step
  has to move, given EACH live row's token count (prompt + generated so
  far): every weight it reads, the caches of those tokens, and any per-row
  state read and written whatever the row's length.
- ``prefill_flops(c, prompt_lens)``: forward FLOPs of prefilling prompts of
  these lengths, the head once per prompt.
- ``prefill_chunk_flops(c, tokens, prompt_lens)``: of ``tokens`` prompt
  tokens taken from such prompts, the head left out.
- ``cache_bytes_per_token(c, bytes_per=2)``: cache bytes one token holds
  over all layers (what PERF.md and the config files quote).

A decoder of attention and mixture-of-experts layers of one kind gets them
from the helpers here and its ``layer_params(c)``; a family whose layers
are of several kinds, run more than once, or attend a window writes its own
sums. Experts HELD here (``held``) and the router's WIDTH (``n_experts``)
are separate counts: a chip that holds its share of the experts computes
that share of each token's ``top_k``."""

from __future__ import annotations

import importlib

#: What every family's module has to define (see above).
REQUIRED = ("decode_step_bytes", "prefill_flops", "prefill_chunk_flops", "cache_bytes_per_token")


def of(family: str):
    """The family's cost module; ``KeyError`` naming the file where it is
    missing or lacks a required function."""
    name = f"benchmarks.costs.{family}"
    try:
        mod = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise KeyError(f"no cost functions for family {family!r}: benchmarks/costs/{family}.py is missing") from None
    lacking = [f for f in REQUIRED if not callable(getattr(mod, f, None))]
    if lacking:
        raise KeyError(f"benchmarks/costs/{family}.py defines no {', '.join(lacking)}")
    return mod


def _optional(family: str, name: str):
    fn = getattr(of(family), name, None)
    if fn is None:
        raise KeyError(f"benchmarks/costs/{family}.py defines no {name}")
    return fn


# ------------------------------------------------- by family (public)


def layer_params(family: str, c: dict) -> dict:
    """Matmul parameters of the configuration's parts (norms left out):
    per-layer attention, dense FFN, one routed expert, shared experts,
    router; and embedding and head. Of a family whose layers are of one
    kind; others define none."""
    return _optional(family, "layer_params")(c)


def active_matmul_params(family: str, c: dict) -> int:
    """Parameters one token multiplies with in a forward pass (the head
    included, the embedding lookup not)."""
    return _optional(family, "active_matmul_params")(c)


def cache_bytes_per_token(family: str, c: dict, bytes_per: int = 2) -> int:
    return of(family).cache_bytes_per_token(c, bytes_per)


def prefill_flops(family: str, c: dict, prompt_lens) -> float:
    return of(family).prefill_flops(c, prompt_lens)


def prefill_chunk_flops(family: str, c: dict, tokens: int, prompt_lens) -> float:
    return of(family).prefill_chunk_flops(c, tokens, prompt_lens)


def decode_step_bytes(family: str, c: dict, rows: int, live_tokens, bytes_per: int = 2) -> float:
    """``live_tokens`` is each live row's token count (``rows`` of them),
    or their sum, which is then spread evenly over the rows: the same
    bytes for a cache that grows by the token, and the nearest there is to
    say for one that does not."""
    if isinstance(live_tokens, int):
        if live_tokens and not rows:
            raise ValueError(f"{live_tokens} live tokens in no row")
        q, r = divmod(live_tokens, rows or 1)
        live_tokens = [q + 1] * r + [q] * (rows - r)
    elif len(live_tokens) != rows:
        raise ValueError(f"{rows} live rows but {len(live_tokens)} token counts")
    return of(family).decode_step_bytes(c, list(live_tokens), bytes_per)


# ------------------------------------------------- helpers for a family


def expected_experts_touched(n_experts: int, top_k: int, rows: int, held: int | None = None) -> float:
    """Expected number of distinct experts, of the ``held`` here (all
    ``n_experts`` of the router's width unless given), that a step of
    ``rows`` tokens reaches under uniform routing."""
    return (n_experts if held is None else held) * (1.0 - (1.0 - top_k / n_experts) ** rows)


def moe_active_params(p: dict) -> int:
    """``active_matmul_params`` from a ``layer_params`` dict: of the
    ``top_k`` experts a token is routed to, the share held here."""
    routed = p["top_k"] * p["expert"] * p.get("held", p["n_experts"]) // p["n_experts"]
    per_moe = p["attn"] + routed + p["shared"] + p["router"]
    return p["n_dense"] * (p["attn"] + p["dense_ffn"]) + p["n_moe"] * per_moe + p["head"]


def causal_prefill_flops(p: dict, per_key: int, n_layers: int, prompt_lens) -> float:
    """2 per active parameter per token, plus causal attention scores and
    values (``per_key`` multiply-adds per query-key pair per layer, n (n +
    1) / 2 pairs). The head runs once per prompt (the last position)."""
    body = moe_active_params(p) - p["head"]
    total = 0.0
    for n in prompt_lens:
        total += 2.0 * body * n + 2.0 * p["head"]
        total += 2.0 * per_key * n_layers * n * (n + 1) / 2.0
    return total


def chunk_share(whole: float, head: int, tokens: int, prompt_lens) -> float:
    """``prefill_chunk_flops`` from the prompts' ``prefill_flops``: 2 per
    active parameter per token, and the attention of each at the mean
    number of keys a token of those prompts attends. The head is left out:
    it is needed once per prompt, whatever number of chunks the prompt
    took."""
    return (whole - 2.0 * head * len(prompt_lens)) * tokens / sum(prompt_lens)


def moe_decode_weight_bytes(p: dict, hidden: int, rows: int, bytes_per: int = 2) -> float:
    """Weight bytes one decode step has to read: the experts routed to
    (expected under uniform routing over ``rows`` live rows, among those
    held here), every other weight once, one row of the embedding per live
    row."""
    touched = expected_experts_touched(p["n_experts"], p["top_k"], rows, p.get("held")) if rows else 0.0
    per_moe = p["attn"] + touched * p["expert"] + p["shared"] + p["router"]
    weights = p["n_dense"] * (p["attn"] + p["dense_ffn"]) + p["n_moe"] * per_moe + p["head"]
    weights += rows * hidden
    return weights * bytes_per
