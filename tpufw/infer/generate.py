"""Autoregressive generation: KV-cache prefill + lax.scan decode, jitted.

TPU-first shape discipline: prompts are LEFT-padded to one static length,
the KV cache is a fixed [B, max_seq_len] ring of slots, and the decode loop
is a ``lax.scan`` over a static number of steps — one compiled program
regardless of prompt lengths or early EOS (finished rows keep stepping but
their outputs are frozen to ``pad_id``; masking, not control flow). The
reference has no inference stack to mirror (workload is ``nvidia-smi``,
reference ``README.md:314``) — this is the serving half a complete
framework needs next to the trainer.

Left-padding is what makes ragged batches one program: every live token
sits flush against the cache cursor, RoPE positions are slot - pad_len,
and pad slots carry segment 0 so attention never sees them
(tpufw.ops.kv_store).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tpufw.infer.sampling import SamplingConfig, sample_token


def cast_decode_params(params, dtype=jnp.bfloat16):
    """Serving-precision cast: float32 weights -> ``dtype``.

    Decode streams every weight once per token, so fp32 ``param_dtype``
    (the training default — fp32 master weights) DOUBLES the
    HBM-bandwidth bill of the bandwidth-bound phase for no serving
    benefit; the matmuls already compute in ``cfg.dtype``. The only
    leaves kept fp32 are int8 quant scales — identified by their
    ``q_kernel`` SIBLING, not by name, since flax RMSNorm weights are
    also called ``scale`` and those SHOULD cast."""

    def walk(node):
        if isinstance(node, dict):
            is_quant = "q_kernel" in node
            return {
                k: v if (is_quant and k == "scale") else walk(v)
                for k, v in node.items()
            }
        if getattr(node, "dtype", None) == jnp.float32:
            return node.astype(dtype)
        return node

    return walk(params)


def pad_prompts(
    prompts: Sequence[Sequence[int]], pad_id: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Left-pad ragged prompts to [B, max_len]; returns (tokens, pad_lens)."""
    max_len = max(len(p) for p in prompts)
    out = np.full((len(prompts), max_len), pad_id, np.int32)
    pads = np.zeros((len(prompts),), np.int32)
    for i, p in enumerate(prompts):
        pads[i] = max_len - len(p)
        if len(p):
            out[i, pads[i]:] = np.asarray(p, np.int32)
    return out, pads


def prefill_cache(
    apply,
    prompt_tokens: jax.Array,
    positions: jax.Array,
    seg: jax.Array,
    prefill_chunk_size: Optional[int],
):
    """Prefill: the whole (padded) prompt through the cache — one pass,
    or fixed-size chunks under ``prefill_chunk_size`` (the cache cursor
    advances per chunk; slot-ordered causality makes chunked and
    one-shot prefill write identical caches). Left-padding makes the
    last column the final real token of every row either way.
    ``apply(cache, tokens, positions, seg) -> (logits, cache)``; ONE
    copy shared by ``generate`` and ``speculative_generate`` so the
    long-prompt lever can't drift between plain and speculative
    serving. Full chunks run under ONE ``lax.scan`` program (O(1)
    trace cost regardless of prompt length); an indivisible tail adds
    at most one remainder program."""
    b, p = prompt_tokens.shape
    if not (prefill_chunk_size is not None and 1 <= prefill_chunk_size < p):
        return apply({}, prompt_tokens, positions, seg)
    c = prefill_chunk_size
    n_full = p // c
    # Chunk 0 outside the scan: its apply CREATES the cache
    # variables the scan then carries.
    logits, cache = apply(
        {}, prompt_tokens[:, :c], positions[:, :c], seg[:, :c]
    )

    def mid(a, n):  # [B, (n)*c] -> [n, B, c]
        return a[:, c: (n + 1) * c].reshape(b, n, c).swapaxes(0, 1)

    if n_full > 1:
        def chunk_step(carry, xs):
            cache, _ = carry
            tok_c, pos_c, seg_c = xs
            lg, cache = apply(cache, tok_c, pos_c, seg_c)
            return (cache, lg), None

        # Logits ride the CARRY (each chunk overwrites), so the
        # scan never stacks a [n_chunks, B, c, V] output.
        (cache, logits), _ = jax.lax.scan(
            chunk_step,
            (cache, logits),
            (
                mid(prompt_tokens, n_full - 1),
                mid(positions, n_full - 1),
                mid(seg, n_full - 1),
            ),
        )
    if p % c:
        s = n_full * c
        logits, cache = apply(
            cache, prompt_tokens[:, s:], positions[:, s:], seg[:, s:]
        )
    return logits, cache


@partial(
    jax.jit,
    static_argnames=(
        "model", "max_new_tokens", "sampling", "pad_id", "eos_id",
        "prefill_chunk_size",
    ),
)
def generate(
    model,
    params,
    prompt_tokens: jax.Array,
    pad_lens: jax.Array,
    rng: jax.Array,
    *,
    max_new_tokens: int,
    sampling: SamplingConfig = SamplingConfig(),
    pad_id: int = 0,
    eos_id: Optional[int] = None,
    prefill_chunk_size: Optional[int] = None,
    live_rows: Optional[jax.Array] = None,
) -> jax.Array:
    """Generate continuations. Returns [B, max_new_tokens] int32.

    Args:
      model: a decode-mode module (``Llama(cfg.decode_config())`` or
        ``Mixtral(...)``) — must populate the "cache" collection.
      params: trained params (the training-mode tree; identical structure).
      prompt_tokens: [B, P] int32, LEFT-padded (see ``pad_prompts``).
      pad_lens: [B] int32 pad count per row.
      rng: sampling key (unused for greedy).
      max_new_tokens: static decode length; rows that hit ``eos_id`` emit
        ``pad_id`` from then on.
      prefill_chunk_size: process the prompt through the cache in
        chunks of this many positions instead of one [B, P] forward —
        prefill's transient activations then scale with the CHUNK, not
        the prompt (the long-prompt serving lever; attention still sees
        every cached earlier chunk). Full chunks run under ONE
        ``lax.scan`` program (O(1) trace cost regardless of prompt
        length); an indivisible tail adds at most one remainder
        program. No padding, no extra cache slots; a chunk >= the
        prompt degrades to the one-shot path.
      live_rows: optional [B] bool mask; False rows (batch fillers —
        pow-2 padding, length-bucket sentinels) start done, so they
        emit ``pad_id`` from step 1 instead of decoding garbage and,
        in the streaming path, never hold up the all-done early exit.
    """
    b, p = prompt_tokens.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    max_seq = getattr(getattr(model, "cfg", None), "max_seq_len", None)
    # Only p + max_new_tokens - 1 slots are written (the final sampled
    # token is never fed back). Past max_seq_len the cache cursor clamps
    # and silently overwrites the last slot — fail at trace time instead.
    if max_seq is not None and p + max_new_tokens - 1 > max_seq:
        raise ValueError(
            f"prompt ({p}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"the KV cache (max_seq_len={max_seq})"
        )
    cache, first, pos0, done, seen, step_rngs = _prefill_and_first(
        model, params, prompt_tokens, pad_lens, rng,
        n_step_keys=max_new_tokens - 1, sampling=sampling,
        eos_id=eos_id, prefill_chunk_size=prefill_chunk_size,
        live_rows=live_rows,
    )
    if max_new_tokens == 1:
        return first[:, None]
    step = _decode_step(
        _model_apply(model, params), b,
        sampling=sampling, pad_id=pad_id, eos_id=eos_id,
    )
    (_, _, _, _, _), rest = jax.lax.scan(
        step, (cache, first, pos0, done, seen), step_rngs
    )
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def _model_apply(model, params):
    """The ONE cached-decode apply closure (mirrors the copy
    ``speculative_generate`` binds): tokens through the model with the
    cache collection mutable, MoE aux dropped."""

    def apply(cache, tokens, positions, seg):
        out, vars_ = model.apply(
            {"params": params, **cache},
            tokens,
            positions=positions,
            segment_ids=seg,
            mutable=["cache"],
        )
        logits = out[0] if isinstance(out, tuple) else out
        return logits, {"cache": vars_["cache"]}

    return apply


def split_prefill_keys(rng: jax.Array, n_step_keys: int):
    """THE key-split contract, extracted so every prefill flavor
    (``_prefill_and_first`` here, the prefix-shared suffix prefill in
    tpufw.infer.pages) derives identical keys from the same ``rng``:
    first = split(rng)[1], step i = split(split(rng)[0], n)[i-1] with
    n = max(n_step_keys, 1) — split(rng, n)[i] is NOT stable across n
    on every jax version, so parity consumers must reproduce this
    exact split count. Returns (first_rng, step_keys)."""
    next_rng, first_rng = jax.random.split(rng)
    return first_rng, jax.random.split(next_rng, max(n_step_keys, 1))


def _prefill_and_first(
    model,
    params,
    prompt_tokens: jax.Array,
    pad_lens: jax.Array,
    rng: jax.Array,
    *,
    n_step_keys: int,
    sampling: SamplingConfig,
    eos_id: Optional[int],
    prefill_chunk_size: Optional[int],
    live_rows: Optional[jax.Array] = None,
):
    """ONE copy of the prefill + first-token + key-split discipline,
    shared by ``generate`` and the streaming path — streamed chunks are
    bit-identical to the one-shot decode BY CONSTRUCTION, not by
    hand-synced duplicates (same rule as ``prefill_cache``'s sharing
    with the speculative path). Key order: first = split(rng)[1],
    step i = split(split(rng)[0], n)[i-1]; split(rng, n)[i] is NOT
    stable across n on every jax version, so every bit-parity consumer
    (streaming, speculative) must reproduce this exact split count,
    n = max(max_new_tokens - 1, 1). Returns
    (cache, first, pos0, done0, seen, step_keys); ``seen`` is None
    unless the repetition penalty needs the [B, V] presence mask (it
    costs B*V bools in the decode carry)."""
    b, p = prompt_tokens.shape
    seg = (jnp.arange(p)[None, :] >= pad_lens[:, None]).astype(jnp.int32)
    positions = jnp.maximum(jnp.arange(p)[None, :] - pad_lens[:, None], 0)
    apply = _model_apply(model, params)
    logits, cache = prefill_cache(
        apply, prompt_tokens, positions, seg, prefill_chunk_size
    )
    track_seen = (
        sampling.repetition_penalty is not None
        and sampling.repetition_penalty != 1.0
    )
    seen = None
    if track_seen:
        vocab = logits.shape[-1]
        real = seg > 0  # seg is always built above; 0 marks padding
        seen = (
            jnp.zeros((b, vocab), bool)
            .at[jnp.arange(b)[:, None], prompt_tokens]
            .max(real)
        )
    first_rng, step_keys = split_prefill_keys(rng, n_step_keys)
    first = sample_token(logits[:, -1, :], sampling, first_rng, seen)
    if track_seen:
        seen = seen.at[jnp.arange(b), first].set(True)
    # The EOS token itself is emitted; only rows ALREADY done emit pad.
    done = jnp.zeros((b,), bool) if eos_id is None else first == eos_id
    if live_rows is not None:
        # Filler rows are born done: they emit pad from step 1 and never
        # gate the streaming all-done early exit.
        done = done | ~live_rows
    return cache, first, p - pad_lens, done, seen, step_keys


def _decode_step(apply, b: int, *, sampling, pad_id, eos_id):
    """ONE copy of the decode step body (sample → seen update → pad
    frozen rows → eos), scanned over all keys by ``generate`` and over
    per-chunk key slices by ``_stream_chunk`` — the other half of the
    stream/one-shot bit-parity contract."""
    track_seen = (
        sampling.repetition_penalty is not None
        and sampling.repetition_penalty != 1.0
    )
    ones = jnp.ones((b, 1), jnp.int32)

    def step(carry, rng_step):
        cache, token, pos, done, seen = carry
        logits, cache = apply(cache, token[:, None], pos[:, None], ones)
        nxt = sample_token(logits[:, -1, :], sampling, rng_step, seen)
        if track_seen:
            seen = seen.at[jnp.arange(b), nxt].set(True)
        emitted = jnp.where(done, pad_id, nxt)
        if eos_id is not None:
            done = done | (nxt == eos_id)
        return (cache, emitted, pos + 1, done, seen), emitted

    return step


@partial(
    jax.jit,
    static_argnames=(
        "model", "n_step_keys", "sampling", "eos_id",
        "prefill_chunk_size",
    ),
)
def _stream_prefill(
    model,
    params,
    prompt_tokens: jax.Array,
    pad_lens: jax.Array,
    rng: jax.Array,
    *,
    n_step_keys: int,
    sampling: SamplingConfig,
    eos_id: Optional[int],
    prefill_chunk_size: Optional[int],
    live_rows: Optional[jax.Array] = None,
):
    """Streaming phase 1: jit boundary over the SHARED
    ``_prefill_and_first`` (the bit-parity contract lives there)."""
    return _prefill_and_first(
        model, params, prompt_tokens, pad_lens, rng,
        n_step_keys=n_step_keys, sampling=sampling, eos_id=eos_id,
        prefill_chunk_size=prefill_chunk_size, live_rows=live_rows,
    )


@partial(
    jax.jit,
    static_argnames=("model", "sampling", "pad_id", "eos_id"),
    donate_argnames=("cache", "seen"),
)
def _stream_chunk(
    model,
    params,
    cache,
    token: jax.Array,
    pos: jax.Array,
    done: jax.Array,
    seen: jax.Array,
    keys: jax.Array,
    *,
    sampling: SamplingConfig,
    pad_id: int,
    eos_id: Optional[int],
):
    """Streaming phase 2: decode ``len(keys)`` tokens from the carried
    cache — the SHARED ``_decode_step`` body ``generate`` scans
    (including the emitted-token feedback: done rows feed pad back),
    scanned over this chunk's key slice. One compiled program serves
    every full chunk of a stream AND every later stream with the same
    shapes; the cache/seen buffers are donated so chunks update in
    place."""
    step = _decode_step(
        _model_apply(model, params), token.shape[0],
        sampling=sampling, pad_id=pad_id, eos_id=eos_id,
    )
    (cache, token, pos, done, seen), out = jax.lax.scan(
        step, (cache, token, pos, done, seen), keys
    )
    return cache, token, pos, done, seen, out.T  # [B, chunk]


def generate_stream(
    model,
    params,
    prompts: Sequence[Sequence[int]],
    *,
    max_new_tokens: int,
    chunk_size: int = 16,
    sampling: SamplingConfig = SamplingConfig(),
    pad_id: int = 0,
    eos_id: Optional[int] = None,
    seed: int = 0,
    rng: Optional[jax.Array] = None,
    prefill_chunk_size: Optional[int] = None,
    live_rows: Optional[Sequence[bool]] = None,
):
    """Streaming decode: yields ``[B, n]`` int32 numpy chunks whose
    concatenation is BIT-identical to ``generate``'s output under the
    same rng (greedy, sampled, penalized — every knob), truncated early
    when every row has passed its eos (the dropped tail is all pad).

    The stream pays one host round trip per chunk (the natural yield
    point) instead of per token; every full chunk reuses ONE compiled
    program, so time-to-first-token is prefill + one chunk and the
    steady rate approaches plain decode as chunk_size grows. First
    yield carries ``chunk_size`` tokens (the prefill-sampled token
    plus chunk_size - 1 steps), later yields ``chunk_size``, the tail
    whatever remains.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    max_seq = getattr(getattr(model, "cfg", None), "max_seq_len", None)
    tokens, pads = pad_prompts(prompts, pad_id)
    p = tokens.shape[1]
    if max_seq is not None and p + max_new_tokens - 1 > max_seq:
        raise ValueError(
            f"prompt ({p}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"the KV cache (max_seq_len={max_seq})"
        )
    if rng is None:
        rng = jax.random.key(seed)
    cache, token, pos, done, seen, step_keys = _stream_prefill(
        model,
        params,
        jnp.asarray(tokens),
        jnp.asarray(pads),
        rng,
        n_step_keys=max_new_tokens - 1,
        sampling=sampling,
        eos_id=eos_id,
        prefill_chunk_size=prefill_chunk_size,
        live_rows=(
            None if live_rows is None
            else jnp.asarray(np.asarray(live_rows, bool))
        ),
    )
    first = np.asarray(token)[:, None]
    if max_new_tokens == 1:
        yield first
        return
    emitted = 1
    head: Optional[np.ndarray] = first  # rides the first yield
    if chunk_size == 1:
        # A 1-token chunk can't carry the head plus a step: the
        # prefill-sampled token IS the first chunk.
        yield head
        head = None
        if eos_id is not None and bool(np.asarray(done).all()):
            return
    while emitted < max_new_tokens:
        n = min(
            chunk_size - 1 if head is not None else chunk_size,
            max_new_tokens - emitted,
        )
        # tpulint: disable=TPU007 -- the key slice's tail chunk
        # (n < chunk_size) is the ONE deliberately distinct shape per
        # stream; every full chunk reuses a single compiled program
        # (TRACE_COUNTS-asserted in tests), so the program ladder is
        # bounded by design, not churn.
        cache, token, pos, done, seen, out = _stream_chunk(
            model,
            params,
            cache,
            token,
            pos,
            done,
            seen,
            step_keys[emitted - 1: emitted - 1 + n],
            sampling=sampling,
            pad_id=pad_id,
            eos_id=eos_id,
        )
        chunk = np.asarray(out)
        if head is not None:
            chunk = np.concatenate([head, chunk], axis=1)
            head = None
        emitted += n
        yield chunk
        # After-yield: once every row is past eos the remaining
        # emissions are all pad — stop instead of decoding dead air.
        if eos_id is not None and bool(np.asarray(done).all()):
            return


def generate_text_stream(
    model,
    params,
    prompts: Sequence[Sequence[int]],
    *,
    max_new_tokens: int,
    chunk_size: int = 16,
    sampling: SamplingConfig = SamplingConfig(),
    pad_id: int = 0,
    eos_id: Optional[int] = None,
    seed: int = 0,
    prefill_chunk_size: Optional[int] = None,
    live_rows: Optional[Sequence[bool]] = None,
):
    """Ragged streaming wrapper: yields, per chunk, one ``list[int]``
    of NEW tokens per row — rows stop emitting after their eos (the
    eos itself is included), mirroring ``generate_text``'s truncation
    row by row. Concatenating a row's chunks equals the row
    ``generate_text`` returns."""
    row_done = [False] * len(prompts)
    for chunk in generate_stream(
        model, params, prompts,
        max_new_tokens=max_new_tokens, chunk_size=chunk_size,
        sampling=sampling, pad_id=pad_id, eos_id=eos_id, seed=seed,
        prefill_chunk_size=prefill_chunk_size, live_rows=live_rows,
    ):
        out: list[list[int]] = []
        for i, row in enumerate(chunk):
            toks = [] if row_done[i] else row.tolist()
            if eos_id is not None and not row_done[i] and eos_id in toks:
                toks = toks[: toks.index(eos_id) + 1]
                row_done[i] = True
            out.append(toks)
        yield out


def generate_text(
    model,
    params,
    prompts: Sequence[Sequence[int]],
    *,
    max_new_tokens: int,
    sampling: SamplingConfig = SamplingConfig(),
    pad_id: int = 0,
    eos_id: Optional[int] = None,
    seed: int = 0,
    prefill_chunk_size: Optional[int] = None,
    live_rows: Optional[Sequence[bool]] = None,
) -> list[list[int]]:
    """Convenience wrapper: ragged python prompts in, ragged lists out."""
    tokens, pads = pad_prompts(prompts, pad_id)
    out = generate(
        model,
        params,
        jnp.asarray(tokens),
        jnp.asarray(pads),
        jax.random.key(seed),
        max_new_tokens=max_new_tokens,
        sampling=sampling,
        pad_id=pad_id,
        eos_id=eos_id,
        prefill_chunk_size=prefill_chunk_size,
        live_rows=(
            None if live_rows is None
            else jnp.asarray(np.asarray(live_rows, bool))
        ),
    )
    result = []
    for row in np.asarray(out):
        toks = row.tolist()
        if eos_id is not None and eos_id in toks:
            toks = toks[: toks.index(eos_id) + 1]
        result.append(toks)
    return result
