"""Speculative decoding: draft proposes, target verifies in one pass.

Autoregressive decode is HBM-bandwidth-bound — every emitted token
streams every target weight once. Speculative decoding spends a small
draft model's tokens to buy back target bandwidth: the draft proposes
``k`` tokens autoregressively, the target scores ALL of them in ONE
cached forward (k+1 tokens through the weights instead of k+1 separate
full-weight streams), and the longest accepted prefix is kept plus one
token from the target's own distribution. Worst case one token per
iteration (plain decode cost + draft overhead); best case k+1.

Two acceptance modes, selected by ``sampling.temperature``:

- **Greedy** (temperature 0): accept while the draft token equals the
  target argmax — the output is EXACTLY the target model's greedy
  continuation, pinned against ``tpufw.infer.generate`` in
  tests/test_speculative.py.
- **Stochastic** (temperature > 0): the rejection-resample scheme.
  Draft token ``x_j ~ q_j`` is accepted iff ``u_j < p_j(x_j)/q_j(x_j)``
  (``u_j`` uniform); on first rejection the replacement is drawn from
  the residual ``norm(max(p_j - q_j, 0))``, and when every draft
  survives the bonus comes from ``p_k`` directly. Marginally, each
  emitted token is distributed EXACTLY as target-only sampling — draft
  quality changes speed, never the distribution. ``p``/``q`` are the
  post-transform distributions (temperature/top-k/top-p/min-p/
  repetition_penalty applied to both), so speculation composes with
  EVERY serving sampler knob. The repetition penalty's seen-token
  state is sequential by construction, but sequential-in-k is cheap
  when k is static: the draft updates its mask as it proposes, and
  the verify pass rebuilds the k+1 per-position masks cumulatively
  (seen_j = seen ∪ drafts[:, :j]) — each position's transformed
  target distribution is exactly what ``generate`` would have used at
  that emission index, so the acceptance test and residual stay
  distribution-exact.

RNG discipline: emission index ``n`` consumes the same key
``generate()`` would use for that index (first = split(rng)[1], rest =
split(split(rng)[0], ...)[n-1]), draft proposals draw with the RAW
per-index key, and acceptance/residual draws use fold_in(key, 1)/
fold_in(key, 2). Consequence: with draft == target every proposal is
accepted and the output is BIT-IDENTICAL to ``generate`` under the same
rng — the distributional-equivalence pin in tests/test_speculative.py.

TPU-first shape discipline, mirroring ``generate``:
- the whole loop is one jitted program: ``lax.while_loop`` over
  iterations (dynamic trip count, bounded by max_new_tokens since every
  iteration emits at least one token), static k, static buffer sizes;
- acceptance is uniform across the batch (the min over rows): the
  KV-cache cursor is one scalar. Rows that matched further simply take
  the bonus token — which equals their draft token there, so every row
  still gets its exact greedy continuation;
- cache rollback is O(1) bookkeeping: rewind the scalar ``cache_index``
  and zero ``cached_segment_ids`` beyond it — never-valid slots are
  masked by segment 0 exactly like never-written ones
  (tpufw.ops.kv_store), and the next
  iteration's write overwrites them.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tpufw.infer.generate import (
    _model_apply,
    pad_prompts,
    prefill_cache,
)
from tpufw.infer.sampling import SamplingConfig, sample_token, transform_logits
from tpufw.infer.slots import live_segments
from tpufw.ops.kv_store import CURSOR, SEGMENT, leaf_name, path_role

# Trace-time counters for the CHUNKED slot-pool speculation below —
# same contract as tpufw.infer.slots.TRACE_COUNTS: bumped once per
# (re)trace inside the jitted bodies, so tests can pin "varying accept
# counts and page churn never recompile the verify program".
TRACE_COUNTS: Dict[str, int] = {"spec_verify": 0, "spec_draft_verify": 0}


def _rollback(cache: dict, new_cursor: jax.Array) -> dict:
    """Rewind a decode cache to ``new_cursor`` valid entries: slots at
    or beyond the cursor become segment-0 (masked) and the next write
    lands on them. Keys/values stay — masking, not control flow."""

    def fix(path, leaf):
        kind = path_role(path).kind
        if path_role(path).per_slot:
            raise ValueError(
                f"speculative decoding: cache leaf {leaf_name(path)!r} is "
                "per-slot state or a window layer's ring, which a rejected "
                "draft has already advanced and no cursor can rewind"
            )
        if kind == CURSOR:
            # nn.scan stacks per-layer cursors into [L]; keep the shape.
            return jnp.full(leaf.shape, new_cursor, leaf.dtype)
        if kind == SEGMENT:
            # [*stack, B, S]: mask the trailing slot axis.
            live = jnp.arange(leaf.shape[-1]) < new_cursor
            return jnp.where(live, leaf, 0)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def _cursor(cache: dict) -> jax.Array:
    """The shared cache_index of a decode cache pytree as a scalar
    (nn.scan stacks identical per-layer cursors into [L])."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if path_role(path).kind == CURSOR:
            return jnp.max(leaf)
    raise ValueError("no cache_index in cache pytree")


@partial(
    jax.jit,
    static_argnames=(
        "draft_model", "model", "k", "max_new_tokens", "pad_id", "eos_id",
        "sampling", "prefill_chunk_size",
    ),
)
def speculative_generate(
    draft_model,
    draft_params,
    model,
    params,
    prompt_tokens: jax.Array,
    pad_lens: jax.Array,
    *,
    max_new_tokens: int,
    k: int = 4,
    pad_id: int = 0,
    eos_id: Optional[int] = None,
    live_rows: Optional[jax.Array] = None,
    sampling: SamplingConfig = SamplingConfig(),
    rng: Optional[jax.Array] = None,
    prefill_chunk_size: Optional[int] = None,
) -> tuple[jax.Array, dict]:
    """Decode ``model`` with ``draft_model`` speculation.

    Same contract as ``tpufw.infer.generate`` (left-padded prompts,
    [B, max_new_tokens] out, eos rows freeze to pad) plus a stats dict
    {"iterations", "emitted"} — mean tokens/iteration is the speedup
    diagnostic (k+1 max). Both models must share the tokenizer/vocab.
    With the default greedy ``sampling`` the output is exactly
    ``model``'s greedy continuation regardless of draft quality (only
    speed varies); with ``sampling.temperature > 0`` (``rng`` required)
    each token is rejection-resampled to the target's post-transform
    distribution — see the module docstring for the scheme.

    ``live_rows`` ([B] bool): rows whose acceptance should count toward
    the batch-min. Serving passes False for its shape-bucketing filler
    rows — otherwise a degenerate filler prompt drags every tick's
    acceptance toward zero and the real rows pay the draft overhead for
    ~1 token/iteration. Dead rows' outputs are NOT guaranteed to be
    their greedy continuation (draft tokens past their own match point
    go unvalidated) — the caller must discard them, which is exactly
    what serving's filler-row slicing does.
    """
    b, p = prompt_tokens.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    stochastic = sampling.temperature != 0.0
    if stochastic and rng is None:
        raise ValueError(
            "sampling.temperature > 0 requires an rng key for the "
            "rejection-resample draws"
        )
    track_seen = (
        sampling.repetition_penalty is not None
        and sampling.repetition_penalty != 1.0
    )
    for m, who in ((model, "model"), (draft_model, "draft_model")):
        max_seq = getattr(getattr(m, "cfg", None), "max_seq_len", None)
        # The verify block may overrun the accepted stream by up to k
        # slots before rollback, so budget for it.
        if max_seq is not None and p + max_new_tokens + k > max_seq:
            raise ValueError(
                f"prompt ({p}) + max_new_tokens ({max_new_tokens}) + "
                f"k ({k}) exceeds {who}'s KV cache "
                f"(max_seq_len={max_seq})"
            )

    seg = (jnp.arange(p)[None, :] >= pad_lens[:, None]).astype(jnp.int32)
    positions = jnp.maximum(jnp.arange(p)[None, :] - pad_lens[:, None], 0)

    def apply(m, prm, cache, tokens, pos, sg):
        out, vars_ = m.apply(
            {"params": prm, **cache},
            tokens,
            positions=pos,
            segment_ids=sg,
            mutable=["cache"],
        )
        logits = out[0] if isinstance(out, tuple) else out
        return logits, {"cache": vars_["cache"]}

    # Prefill both models over the (padded) prompt — chunked under
    # prefill_chunk_size (the long-prompt lever, shared with generate).
    t_logits, t_cache = prefill_cache(
        partial(apply, model, params), prompt_tokens, positions, seg,
        prefill_chunk_size,
    )
    _, d_cache = prefill_cache(
        partial(apply, draft_model, draft_params), prompt_tokens,
        positions, seg, prefill_chunk_size,
    )
    # Repetition-penalty seen mask: prompt tokens (padding excluded via
    # seg) — the exact construction generate() uses, so the two loops'
    # transformed distributions match position for position.
    seen0 = None
    if track_seen:
        vocab = t_logits.shape[-1]
        real = seg > 0
        seen0 = (
            jnp.zeros((b, vocab), bool)
            .at[jnp.arange(b)[:, None], prompt_tokens]
            .max(real)
        )
    all_keys = None
    if stochastic:
        # Emission index n consumes the key generate() would use for
        # that index — same split order (first = split(rng)[1], step i
        # = split(split(rng)[0], ...)[i-1]) and, crucially, the SAME
        # split count: split(rng, n)[i] is not stable across n on
        # every jax version, so the shared indices must come from the
        # exact split generate() performs. The k overrun-slack keys
        # cover emission indices >= max_new_tokens, whose draws are
        # sliced off at return — any deterministic stream works there.
        next_rng, first_key = jax.random.split(rng)
        step_keys = jax.random.split(next_rng, max(max_new_tokens - 1, 1))
        # tpulint: disable=TPU003 — fold_in(next_rng, 7) deliberately
        # derives the overrun stream from the already-split parent: the
        # shared prefix must replay generate()'s exact splits (comment
        # above), and the fold_in constant keeps the slack keys disjoint.
        overrun_keys = jax.random.split(jax.random.fold_in(next_rng, 7), k)
        all_keys = jnp.concatenate(
            [first_key[None], step_keys, overrun_keys]
        )
        first = sample_token(
            t_logits[:, -1, :], sampling, first_key, seen0
        )
    else:
        # transform_logits is an identity (up to f32 cast) for greedy
        # without a penalty; with one it applies the seen-mask rule
        # before the argmax, exactly like sample_token at temp 0.
        first = jnp.argmax(
            transform_logits(t_logits[:, -1, :], sampling, seen0),
            axis=-1,
        ).astype(jnp.int32)
    if track_seen:
        seen0 = seen0.at[jnp.arange(b), first].set(True)
    done0 = (
        jnp.zeros((b,), bool) if eos_id is None else first == eos_id
    )

    # Output buffer with k+1 slack: a block write near the end may
    # overrun max_new_tokens; the tail is sliced off at return.
    buf = jnp.full((b, max_new_tokens + k + 1), pad_id, jnp.int32)
    buf = buf.at[:, 0].set(first)  # the eos token itself is emitted
    pos0 = p - pad_lens  # `first`'s RoPE position when fed back, per row

    ones = jnp.ones((b, 1), jnp.int32)

    def draft_propose(d_cache, prev, pos, keys_blk, seen):
        """k proposals + one filler step so the draft cache holds every
        proposed token (the a == k acceptance case needs d_k cached).
        Stochastic proposals draw from the TRANSFORMED draft
        distribution with the raw per-emission-index key (the coupling
        that makes draft == target bit-match ``generate``); the
        distributions are returned for the acceptance ratio test.
        With a repetition penalty the seen mask advances over the
        draft's OWN proposals — its proposal distribution q_j is
        conditioned on the same prefix the target's p_j will be."""
        toks, qs = [], []
        tok = prev
        for i in range(k + 1):
            logits, d_cache = apply(
                draft_model, draft_params, d_cache,
                tok[:, None], (pos + i)[:, None], ones,
            )
            if i < k:
                if stochastic:
                    q_i = transform_logits(
                        logits[:, -1, :], sampling, seen
                    )
                    tok = jax.random.categorical(
                        keys_blk[i], q_i, axis=-1
                    ).astype(jnp.int32)
                    qs.append(q_i)
                elif track_seen:
                    tok = jnp.argmax(
                        transform_logits(
                            logits[:, -1, :], sampling, seen
                        ),
                        axis=-1,
                    ).astype(jnp.int32)
                else:
                    tok = jnp.argmax(
                        logits[:, -1, :], axis=-1
                    ).astype(jnp.int32)
                if track_seen:
                    seen = seen.at[jnp.arange(b), tok].set(True)
                toks.append(tok)
        q_trans = jnp.stack(qs, axis=1) if stochastic else None
        return jnp.stack(toks, axis=1), q_trans, d_cache  # [B, k]

    def body(carry):
        t_cache, d_cache, prev, pos, done, n, buf, iters, seen = carry
        t_cur0 = _cursor(t_cache)
        d_cur0 = _cursor(d_cache)
        keys_blk = (
            jax.lax.dynamic_slice_in_dim(all_keys, n, k + 1)
            if stochastic
            else None
        )
        drafts, q_trans, d_cache = draft_propose(
            d_cache, prev, pos, keys_blk, seen
        )

        # One target pass scores prev + all k drafts: logits[:, i] is
        # the target's next-token distribution after input i.
        verify_in = jnp.concatenate([prev[:, None], drafts], axis=1)
        verify_pos = pos[:, None] + jnp.arange(k + 1)[None, :]
        t_logits, t_cache = apply(
            model, params, t_cache, verify_in, verify_pos,
            jnp.ones((b, k + 1), jnp.int32),
        )

        def transform_positions(logits):
            """Per-position transformed target distributions. Without a
            penalty one vectorized transform covers all k+1 positions;
            with one, position j's mask is seen ∪ drafts[:, :j] —
            built cumulatively over the STATIC k (k+1 [B, V] transforms
            instead of 1; k is small and this is the exactness
            requirement: each position's distribution must equal the
            one generate() would sample at that emission index)."""
            if not track_seen:
                return transform_logits(logits, sampling)
            outs, s = [], seen
            for j in range(k + 1):
                outs.append(
                    transform_logits(logits[:, j], sampling, s)
                )
                if j < k:
                    s = s.at[jnp.arange(b), drafts[:, j]].set(True)
            return jnp.stack(outs, axis=1)

        if stochastic:
            # Rejection test on the post-transform distributions:
            # accept x_j iff u_j < p_j(x_j)/q_j(x_j).
            p_trans = transform_positions(t_logits)  # [B,k+1,V]
            logp = jax.nn.log_softmax(p_trans, axis=-1)
            logq = jax.nn.log_softmax(q_trans, axis=-1)
            lp = jnp.take_along_axis(
                logp[:, :k], drafts[..., None], -1
            )[..., 0]
            lq = jnp.take_along_axis(logq, drafts[..., None], -1)[..., 0]
            us = jnp.stack(
                [
                    jax.random.uniform(
                        jax.random.fold_in(keys_blk[j], 1), (b,)
                    )
                    for j in range(k)
                ],
                axis=1,
            )  # [B, k]
            match = us < jnp.exp(lp - lq)
        else:
            greedy = jnp.argmax(
                transform_positions(t_logits), axis=-1
            ).astype(jnp.int32)  # [B, k+1]
            match = drafts == greedy[:, :k]  # [B, k]

        # Per-row longest accepted prefix, then the batch-uniform min
        # (one scalar cache cursor). Rows that matched further lose
        # nothing: their col-a token is their own ACCEPTED draft.
        row_accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), 1), 1)
        # Rows whose output no longer matters must not throttle the
        # batch min: filler rows (live_rows) never did, and eos-DONE
        # rows' post-eos continuations diverge target-vs-draft forever
        # (their emissions are frozen to pad_id regardless), so without
        # this mask one finished row pins every live row to ~1
        # token/iteration.
        row_accept = jnp.where(done, k, row_accept)
        if live_rows is not None:
            row_accept = jnp.where(live_rows, row_accept, k)
        a = jnp.min(row_accept)  # scalar in [0, k]

        cols = jnp.arange(k + 1)[None, :]
        drafts_pad = jnp.pad(drafts, ((0, 0), (0, 1)))
        if stochastic:
            # Col-a token per row: rows that accepted past a keep their
            # own accepted draft x_a; rows rejected AT a draw from the
            # residual norm(max(p_a - q_a, 0)). When a == k (everyone
            # accepted everything) the where() below bypasses the
            # residual entirely and selects logp_a — the bonus draw
            # straight from the target's p_k — and the RAW
            # index key is used there so it matches generate()'s
            # categorical for that emission index bit-for-bit; the
            # a < k resample folds the key (the raw one was consumed by
            # the draft proposal, and reusing its gumbel noise would
            # correlate the resample with the rejection event).
            # Only the col-a slice is ever drawn from: index FIRST,
            # softmax one [B, V] row (not k+1 of them per iteration —
            # V is the vocab in serving). p_a rides the existing logp.
            # The a == k clamp feeds a real-but-irrelevant q row to the
            # residual branch; the where() below picks logp_a there.
            logp_a = jax.lax.dynamic_index_in_dim(
                logp, a, axis=1, keepdims=False
            )
            p_a = jnp.exp(logp_a)
            q_a = jax.nn.softmax(
                jax.lax.dynamic_index_in_dim(
                    q_trans, jnp.minimum(a, k - 1), axis=1,
                    keepdims=False,
                ),
                axis=-1,
            )
            alt_logits = jnp.where(
                a == k, logp_a, jnp.log(jnp.maximum(p_a - q_a, 0.0))
            )
            key_a = jax.lax.dynamic_index_in_dim(
                keys_blk, a, keepdims=False
            )
            key_used = jax.lax.cond(
                a == k,
                lambda: key_a,
                lambda: jax.random.fold_in(key_a, 2),
            )
            tok_alt = jax.random.categorical(
                key_used, alt_logits, axis=-1
            ).astype(jnp.int32)
            x_a = jax.lax.dynamic_index_in_dim(
                drafts_pad, a, axis=1, keepdims=False
            )
            col_a_tok = jnp.where(row_accept > a, x_a, tok_alt)  # [B]
            block = jnp.where(cols < a, drafts_pad, col_a_tok[:, None])
        else:
            # Emitted block: drafts[0..a-1] then the bonus greedy[a].
            greedy_a = jnp.take_along_axis(
                greedy, jnp.broadcast_to(a[None, None], (b, 1)), 1
            )
            block = jnp.where(cols < a, drafts_pad, greedy_a)
        # [B, k+1]; cols > a are dont-cares (masked below)
        n_block = jnp.minimum(a + 1, max_new_tokens - n)

        # EOS + emission masking: freeze rows after their eos, blank
        # columns beyond this block's length.
        live_col = cols < n_block
        if eos_id is None:
            done_before = jnp.broadcast_to(done[:, None], (b, k + 1))
            new_done = done
        else:
            hits = (block == eos_id) & live_col
            ihits = hits.astype(jnp.int32)
            # done before col j = done at entry, or an eos hit in a
            # STRICTLY earlier column (the eos itself is emitted).
            done_before = done[:, None] | (
                (jnp.cumsum(ihits, axis=1) - ihits) > 0
            )
            new_done = done | jnp.any(hits, axis=1)
        emitted = jnp.where(
            live_col & ~done_before, block, pad_id
        ).astype(jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, emitted, (0, n))

        # Rollback: target verified k+1 inputs but only prev + a drafts
        # are part of the stream; draft processed prev + k drafts, keep
        # prev + a. (The next iteration re-feeds the bonus token to
        # both.)
        t_cache = _rollback(t_cache, t_cur0 + a + 1)
        d_cache = _rollback(d_cache, d_cur0 + a + 1)

        # Next input token = the bonus (block col a, traced index).
        nxt = jax.lax.dynamic_index_in_dim(
            block, a, axis=1, keepdims=False
        )
        if track_seen:
            # Mark this block's emissions (cols < n_block) — the same
            # tokens generate() would have marked one step at a time.
            # Done rows mark their (unvalidated) block values; their
            # outputs are pad-frozen, so the divergence is unobservable.
            seen = seen.at[jnp.arange(b)[:, None], block].max(
                jnp.broadcast_to(live_col, (b, k + 1))
            )
        return (
            t_cache, d_cache, nxt, pos + a + 1, new_done,
            n + n_block, buf, iters + 1, seen,
        )

    def cond(carry):
        return carry[5] < max_new_tokens  # carry[5] = tokens emitted

    if max_new_tokens == 1:
        return buf[:, :1], {
            "iterations": jnp.zeros((), jnp.int32),
            "emitted": jnp.ones((), jnp.int32),
        }

    init = (
        t_cache, d_cache, first, pos0, done0,
        jnp.asarray(1, jnp.int32), buf, jnp.asarray(0, jnp.int32),
        # The seen mask rides the carry (placeholder scalar when the
        # penalty is off, so the loop signature stays uniform).
        seen0 if track_seen else jnp.zeros((), bool),
    )
    *_, n_final, buf, iters, _seen = jax.lax.while_loop(cond, body, init)
    return buf[:, :max_new_tokens], {
        "iterations": iters,
        "emitted": jnp.minimum(n_final, max_new_tokens),
    }


def speculative_generate_text(
    draft_model,
    draft_params,
    model,
    params,
    prompts: Sequence[Sequence[int]],
    *,
    max_new_tokens: int,
    k: int = 4,
    pad_id: int = 0,
    eos_id: Optional[int] = None,
    live_rows: Optional[Sequence[bool]] = None,
    sampling: SamplingConfig = SamplingConfig(),
    seed: int = 0,
    rng: Optional[jax.Array] = None,
    prefill_chunk_size: Optional[int] = None,
) -> tuple[list[list[int]], dict]:
    """Ragged-python convenience wrapper (mirrors ``generate_text``,
    including its ``seed`` knob; an explicit ``rng`` wins over seed).
    Returns (outputs, stats) with stats as plain ints."""
    if rng is None and sampling.temperature != 0.0:
        rng = jax.random.key(seed)
    tokens, pads = pad_prompts(prompts, pad_id)
    out, stats = speculative_generate(
        draft_model,
        draft_params,
        model,
        params,
        jnp.asarray(tokens),
        jnp.asarray(pads),
        max_new_tokens=max_new_tokens,
        k=k,
        pad_id=pad_id,
        eos_id=eos_id,
        live_rows=(
            None if live_rows is None else jnp.asarray(live_rows, bool)
        ),
        sampling=sampling,
        rng=rng,
        prefill_chunk_size=prefill_chunk_size,
    )
    result = []
    for row in np.asarray(out):
        toks = row.tolist()
        if eos_id is not None and eos_id in toks:
            toks = toks[: toks.index(eos_id) + 1]
        result.append(toks)
    return result, {k_: int(v) for k_, v in stats.items()}


# ---------------------------------------------------------------------------
# Chunked slot-pool speculation
# ---------------------------------------------------------------------------
# Everything below makes speculation a first-class citizen of the
# tpufw.infer.slots / tpufw.infer.pages slot pool, replacing the
# whole-batch tick path above for continuous-batching serving:
#
# - ONE verify program per (pool, k): draft k tokens, feed the
#   [token, p_1..p_k] block through the target in a single t=k+1 pass
#   (the models' paged/contiguous decode branches scatter the block
#   then gather it back, so intra-block causality is the same
#   slot-ordered mask), and fold PER-SLOT acceptance into the program
#   as data — accept counts become dynamic cursor advances under the
#   existing done/remaining masks. Occupancy, page tables, accept
#   counts: all DATA, never shapes, so page churn and varying accept
#   counts never retrace (TRACE_COUNTS-pinned, like decode_steps).
# - Rollback is per-slot cursor rewind ONLY: stale segment-1 entries
#   beyond the rewound cursor sit at slots > any future query slot
#   until overwritten in slot order, so the causal mask already hides
#   them (no segment zeroing — that would be a [S, cache_len] write
#   per pass for bookkeeping the mask does for free).
# - Greedy (temperature 0) emissions are argmax of the same float32
#   logits decode_steps takes, so spec-on-slots is BIT-EQUAL to plain
#   decode_steps regardless of accept counts. Stochastic uses per-slot
#   rejection-resampling (distributionally exact, not bit-equal).
# - Self-drafting (ngram_propose) needs no draft model: proposals are
#   host-side prompt-lookup, q is a one-hot, and the accept test
#   degrades to u < p(x_j).
#
# Callers with a repetition penalty are rejected: the penalty makes
# each position's distribution depend on acceptance of every previous
# one, which breaks the one-pass verify factorization. Those pools
# stay on plain chunked decode.


def _pool_cursor(cache: dict, n_slots: int) -> jax.Array:
    """Per-slot cursor vector [S] from a slot-pool cache (any
    cache_index leaf: [S] or nn.scan-stacked [L, S] — rows identical
    by construction)."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if path_role(path).kind == CURSOR:
            return leaf.reshape(-1, n_slots)[0]
    raise ValueError("no cache_index in cache pytree")


def _set_pool_cursor(cache: dict, new: jax.Array) -> dict:
    """Write per-slot cursors ``new`` [S] into every cache_index leaf
    (broadcast over the stacked layer axis when present). Cursor-only:
    see the module comment on mask-covered stale entries."""

    def fix(path, leaf):
        if path_role(path).kind == CURSOR:
            return jnp.broadcast_to(new.astype(leaf.dtype), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def _spec_advance(
    logits, proposals, q_trans, key, token, pos, done, remaining,
    *, sampling, pad_id, eos_id,
):
    """Shared verify tail: target logits [S, k+1, V] for the block
    [token, p_1..p_k] -> per-slot emissions + advanced slot state.

    Emission j is the successor of block position j (so col 0 is the
    token after ``token``, col k the bonus after a full accept). The
    valid mask composes acceptance (col <= accept), the per-slot
    budget, first-EOS-inclusive truncation, and entry done — the same
    masking discipline as _decode_steps_jit, vectorized over the
    block. ``q_trans`` is the draft's transformed logits [S, k, V], or
    None for deterministic proposals (greedy and self-draft: q is a
    one-hot at the proposal).

    Returns (out [S, k+1] pad-masked, n_emit [S], accept [S], token,
    pos, done, remaining).
    """
    s, kp1 = logits.shape[:2]
    k = kp1 - 1
    cols = jnp.arange(kp1)[None, :]
    p_trans = transform_logits(logits, sampling)
    if sampling.temperature == 0.0:
        # Greedy: the target's choice at every block position in one
        # argmax — acceptance only decides how MANY columns are real.
        block = jnp.argmax(p_trans, axis=-1).astype(jnp.int32)
        match = proposals == block[:, :k]
        accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), 1), 1)
    else:
        logp = jax.nn.log_softmax(p_trans, axis=-1)
        lp = jnp.take_along_axis(
            logp[:, :k], proposals[..., None], axis=-1
        )[..., 0]
        if q_trans is None:
            lq = jnp.zeros_like(lp)  # one-hot q: accept iff u < p(x_j)
        else:
            lq = jnp.take_along_axis(
                jax.nn.log_softmax(q_trans, axis=-1),
                proposals[..., None], axis=-1,
            )[..., 0]
        us = jax.random.uniform(jax.random.fold_in(key, 1), (s, k))
        match = jnp.log(us) < (lp - lq)
        accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), 1), 1)
        # Column `accept` resamples: from p on a full accept, else from
        # the residual norm(max(p - q, 0)) at the first rejection (for
        # one-hot q the residual is p with the proposal masked out).
        logp_a = jnp.take_along_axis(
            logp, accept[:, None, None], axis=1
        )[:, 0]
        if q_trans is None:
            x_a = jnp.take_along_axis(
                proposals, jnp.minimum(accept, k - 1)[:, None], axis=1
            )[:, 0]
            residual = logp_a.at[jnp.arange(s), x_a].set(-1e30)
        else:
            q_a = jax.nn.softmax(
                jnp.take_along_axis(
                    q_trans, jnp.minimum(accept, k - 1)[:, None, None],
                    axis=1,
                )[:, 0],
                axis=-1,
            )
            residual = jnp.log(
                jnp.maximum(jnp.exp(logp_a) - q_a, 1e-30)
            )
        alt_logits = jnp.where((accept == k)[:, None], logp_a, residual)
        alt = jax.random.categorical(
            # tpulint: disable=TPU003 — fold_in(key, 2) is a distinct
            # stream from the fold_in(key, 1) acceptance uniforms.
            jax.random.fold_in(key, 2), alt_logits, axis=-1
        ).astype(jnp.int32)
        props_pad = jnp.concatenate(
            [proposals, jnp.zeros((s, 1), jnp.int32)], axis=1
        )
        block = jnp.where(cols < accept[:, None], props_pad, alt[:, None])
    valid = (cols <= accept[:, None]) & (cols < remaining[:, None])
    hits = None
    if eos_id is not None:
        hits = (block == eos_id) & valid
        ih = hits.astype(jnp.int32)
        # Inclusive first-EOS truncation: the EOS itself is delivered,
        # everything after it in the block is masked.
        valid = valid & ((jnp.cumsum(ih, axis=1) - ih) == 0)
    emit = valid & ~done[:, None]
    out = jnp.where(emit, block, pad_id).astype(jnp.int32)
    n_emit = emit.sum(axis=1).astype(jnp.int32)
    accept = jnp.where(done, 0, accept).astype(jnp.int32)
    remaining = jnp.where(done, remaining, remaining - n_emit)
    newly = remaining <= 0
    if eos_id is not None:
        newly = newly | jnp.any(hits & emit, axis=1)
    # Next feed = last emitted token; a live row always emits >= 1
    # (col 0 is acceptance-free and budget >= 1 while live).
    last = jnp.maximum(n_emit - 1, 0)
    nxt = jnp.take_along_axis(block, last[:, None], axis=1)[:, 0]
    token = jnp.where(done, pad_id, nxt).astype(jnp.int32)
    pos = jnp.where(done, pos, pos + n_emit)
    return out, n_emit, accept, token, pos, done | newly, remaining


@partial(
    jax.jit,
    static_argnames=("model", "sampling", "pad_id", "eos_id"),
    donate_argnames=("cache", "token", "pos", "done", "remaining"),
)
def _spec_verify_jit(
    model, params, cache, token, pos, done, remaining, proposals, key,
    *, sampling, pad_id, eos_id,
):
    """Verify host-supplied proposals [S, k] in ONE t=k+1 target pass
    and advance the pool. Self-drafting path (n-gram / prompt-lookup):
    q is a one-hot at the proposal."""
    TRACE_COUNTS["spec_verify"] += 1
    apply = _model_apply(model, params)
    s, k = proposals.shape
    cur0 = _pool_cursor(cache, s)
    block_in = jnp.concatenate([token[:, None], proposals], axis=1)
    positions = pos[:, None] + jnp.arange(k + 1)[None, :]
    logits, cache = apply(
        cache, block_in, positions, live_segments(done, k + 1)
    )
    out, n_emit, accept, token, pos, done_new, remaining = _spec_advance(
        logits, proposals, None, key, token, pos, done, remaining,
        sampling=sampling, pad_id=pad_id, eos_id=eos_id,
    )
    # Rollback = cursor rewind (done rows pinned at entry cursor).
    cache = _set_pool_cursor(cache, jnp.where(done, cur0, cur0 + n_emit))
    return cache, token, pos, done_new, remaining, out, n_emit, accept


@partial(
    jax.jit,
    static_argnames=(
        "model", "draft_model", "k", "sampling", "pad_id", "eos_id",
    ),
    donate_argnames=(
        "cache", "d_cache", "token", "pos", "done", "remaining",
    ),
)
def _spec_draft_verify_jit(
    model, params, draft_model, draft_params, cache, d_cache,
    token, pos, done, remaining, key,
    *, k, sampling, pad_id, eos_id,
):
    """Fused draft+verify: k single-token draft passes propose, one
    t=k+1 target pass verifies, and BOTH pools' cursors advance in
    lockstep by the per-slot emit count. The draft cache ingests
    [token, p_1..p_{k-1}] — exactly the entries that are correct for
    any accepted prefix — so rewinding its cursor by the same n_emit
    keeps it one-entry behind the target (the next pass feeds the
    corrected last token to both), and no draft entry ever needs
    patching."""
    TRACE_COUNTS["spec_draft_verify"] += 1
    apply = _model_apply(model, params)
    d_apply = _model_apply(draft_model, draft_params)
    s = token.shape[0]
    cur0 = _pool_cursor(cache, s)
    d_cur0 = _pool_cursor(d_cache, s)
    stochastic = sampling.temperature != 0.0
    seg1 = live_segments(done, 1)
    draft_keys = (
        jax.random.split(jax.random.fold_in(key, 3), k)
        if stochastic else None
    )
    toks, qs = [], []
    tok = token
    for i in range(k):
        d_logits, d_cache = d_apply(
            d_cache, tok[:, None], (pos + i)[:, None], seg1
        )
        if stochastic:
            q_i = transform_logits(d_logits[:, -1, :], sampling)
            tok = jax.random.categorical(
                draft_keys[i], q_i, axis=-1
            ).astype(jnp.int32)
            qs.append(q_i)
        else:
            tok = jnp.argmax(
                d_logits[:, -1, :].astype(jnp.float32), axis=-1
            ).astype(jnp.int32)
        toks.append(tok)
    proposals = jnp.stack(toks, axis=1)  # [S, k]
    q_trans = jnp.stack(qs, axis=1) if stochastic else None
    block_in = jnp.concatenate([token[:, None], proposals], axis=1)
    positions = pos[:, None] + jnp.arange(k + 1)[None, :]
    logits, cache = apply(
        cache, block_in, positions, live_segments(done, k + 1)
    )
    # tpulint: disable=TPU003 — _spec_advance folds key with constants
    # 1/2, disjoint from the fold_in(key, 3) draft split above.
    out, n_emit, accept, token, pos, done_new, remaining = _spec_advance(
        logits, proposals, q_trans, key, token, pos, done, remaining,
        sampling=sampling, pad_id=pad_id, eos_id=eos_id,
    )
    cache = _set_pool_cursor(cache, jnp.where(done, cur0, cur0 + n_emit))
    d_cache = _set_pool_cursor(
        d_cache, jnp.where(done, d_cur0, d_cur0 + n_emit)
    )
    return (
        cache, d_cache, token, pos, done_new, remaining, out, n_emit,
        accept,
    )


def _reject_penalty(sampling: SamplingConfig) -> None:
    if (
        sampling.repetition_penalty is not None
        and sampling.repetition_penalty != 1.0
    ):
        raise ValueError(
            "speculative slot-pool decode does not compose with a "
            "repetition penalty (acceptance at position j would change "
            "the penalized distribution at j+1, breaking the one-pass "
            "verify) — use plain decode_steps for penalty pools"
        )


def spec_verify_steps(pool, proposals, key):
    """One self-draft speculative pass over ``pool`` (a SlotPool /
    PagedSlotPool): verify host proposals [S, k], advance the pool,
    return (out [S, k+1], n_emit [S], accept [S]) as device arrays."""
    _reject_penalty(pool.sampling)
    proposals = jnp.asarray(proposals, jnp.int32)
    perf = getattr(pool, "perf", None)
    if perf is not None:
        perf.observe_jit(
            f"serve_spec_k{proposals.shape[1]}",
            _spec_verify_jit,
            (
                pool.model, pool.params, pool.cache, pool.token,
                pool.pos, pool.done, pool.remaining, proposals, key,
            ),
            kwargs=dict(
                sampling=pool.sampling, pad_id=pool.pad_id,
                eos_id=pool.eos_id,
            ),
        )
    (
        pool.cache, pool.token, pool.pos, pool.done, pool.remaining,
        out, n_emit, accept,
    ) = _spec_verify_jit(
        pool.model, pool.params, pool.cache, pool.token, pool.pos,
        pool.done, pool.remaining, proposals, key,
        sampling=pool.sampling, pad_id=pool.pad_id, eos_id=pool.eos_id,
    )
    return out, n_emit, accept


def spec_draft_steps(pool, draft_pool, key, k: int):
    """One fused draft+verify pass: ``draft_pool`` (same n_slots,
    cursors in lockstep with ``pool``) proposes k tokens, the target
    verifies. Returns (out [S, k+1], n_emit [S], accept [S])."""
    _reject_penalty(pool.sampling)
    perf = getattr(pool, "perf", None)
    if perf is not None:
        perf.observe_jit(
            f"serve_spec_draft_k{k}",
            _spec_draft_verify_jit,
            (
                pool.model, pool.params, draft_pool.model,
                draft_pool.params, pool.cache, draft_pool.cache,
                pool.token, pool.pos, pool.done, pool.remaining, key,
            ),
            kwargs=dict(
                k=k, sampling=pool.sampling, pad_id=pool.pad_id,
                eos_id=pool.eos_id,
            ),
        )
    (
        pool.cache, draft_pool.cache, pool.token, pool.pos, pool.done,
        pool.remaining, out, n_emit, accept,
    ) = _spec_draft_verify_jit(
        pool.model, pool.params, draft_pool.model, draft_pool.params,
        pool.cache, draft_pool.cache, pool.token, pool.pos, pool.done,
        pool.remaining, key,
        k=k, sampling=pool.sampling, pad_id=pool.pad_id,
        eos_id=pool.eos_id,
    )
    return out, n_emit, accept


def ngram_propose(
    history: Sequence[int], k: int, *, max_n: int = 3, pad_id: int = 0
) -> List[int]:
    """Prompt-lookup self-drafting (host-side, O(len * n) per call):
    match the longest trailing n-gram (n = max_n..1) of ``history``
    against its earlier occurrences and propose the k tokens that
    followed the MOST RECENT match. A cold miss returns pad fill — the
    verify pass then accepts 0 columns and the pass degrades to plain
    single-token yield, never to a wrong emission."""
    h = list(history)
    length = len(h)
    for n in range(min(max_n, length - 1), 0, -1):
        tail = h[length - n:]
        for i in range(length - n - 1, -1, -1):
            if h[i:i + n] == tail:
                cont = h[i + n:i + n + k]
                if cont:
                    return (cont + [pad_id] * (k - len(cont)))[:k]
    return [pad_id] * k


class AcceptEMA:
    """Per-slot EMA of the accepted-draft fraction (accept / k) — the
    host-side signal behind acceptance-aware scheduling. Slots start
    OPTIMISTIC (EMA 1.0 on occupy) so every request gets at least one
    speculative pass; the pool runs spec while the mean EMA over
    active slots clears ``min_accept``, and otherwise falls back to
    plain chunked decode, re-probing with one spec pass every
    ``probe_every`` fallback chunks (0 disables probing — draft-model
    pools set this, because plain chunks leave the draft KV stale and
    a probe would measure the stale-context draft)."""

    def __init__(
        self,
        n_slots: int,
        *,
        alpha: float = 0.25,
        min_accept: float = 0.25,
        probe_every: int = 8,
    ) -> None:
        self.alpha = float(alpha)
        self.min_accept = float(min_accept)
        self.probe_every = int(probe_every)
        self.ema: List[Optional[float]] = [None] * n_slots
        self._since_spec = 0

    def occupy(self, slot: int) -> None:
        self.ema[slot] = 1.0

    def vacate(self, slot: int) -> None:
        self.ema[slot] = None

    def update(self, slot: int, frac: float) -> None:
        prev = self.ema[slot]
        if prev is None:
            prev = 1.0
        self.ema[slot] = (1.0 - self.alpha) * prev + self.alpha * float(
            frac
        )

    def fallback_slots(self, slots: Sequence[int]) -> int:
        """Active slots currently below the acceptance threshold."""
        return sum(
            1
            for s in slots
            if self.ema[s] is not None and self.ema[s] < self.min_accept
        )

    def use_spec(self, slots: Sequence[int]) -> bool:
        vals = [self.ema[s] for s in slots if self.ema[s] is not None]
        if not vals:
            return False
        if sum(vals) / len(vals) >= self.min_accept:
            self._since_spec = 0
            return True
        self._since_spec += 1
        if self.probe_every and self._since_spec >= self.probe_every:
            self._since_spec = 0
            return True
        return False
