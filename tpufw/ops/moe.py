"""Top-k capacity-bounded MoE routing as pure einsum algebra.

One routing implementation shared by the flax MoE layer
(``tpufw.models.mixtral.MoEMLP``) and the functional pipeline MoE block
(``tpufw.parallel.pipeline``): the reference has no MoE (or any ML) at
all — expert parallelism enters via BASELINE config 5 — and the whole
point of the einsum formulation is that the dispatch/combine tensors ARE
the communication: sharding the expert axis makes XLA emit the
all-to-alls/psums, no per-expert Python and no hand-written send/recv
(SURVEY.md §2c).

The capacity discipline is GShard-style: per routing group of G tokens,
each expert accepts at most C slots; assignment priority is expert slot 0
of every token over slot 1, earlier tokens over later ones. Overflowing
assignments are dropped (the residual stream carries those tokens
unchanged).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


def expert_capacity(g: int, k: int, e: int, capacity_factor: float) -> int:
    """Per-expert slot count for a routing group of ``g`` tokens:
    ``capacity_factor`` x the perfectly-balanced load (g*k/e), never
    below ``k``. ONE definition for the flax and pipelined MoE paths —
    capacity determines which tokens drop, so a drift here would
    silently change drop behavior in only one path."""
    return max(int(capacity_factor * g * k / e), k)


def _topk_select(
    router_logits: jax.Array,
    k: int,
    norm_topk: bool,
    group_limit: Optional[tuple[int, int]],
    scoring: str = "softmax",
    select_bias: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared selection front half of both routing implementations:
    scores, optional DeepSeek group-limited masking, top-k, optional
    top-k renormalization. Returns (probs [G,E], topk_probs [G,k],
    topk_idx [G,k]).

    ``scoring="softmax"`` scores by softmax over the experts;
    ``"sigmoid"`` scores each expert alone (the DeepSeek-V3 / GLM-4.5
    convention): the k experts are CHOSEN by score + ``select_bias``
    [E], and weighed by the score without it."""
    g, e = router_logits.shape
    if scoring == "sigmoid":
        if group_limit is not None:
            raise NotImplementedError(
                "sigmoid scoring with group-limited selection"
            )
        scores = jax.nn.sigmoid(router_logits)  # [G, E]
        biased = scores if select_bias is None else scores + select_bias
        _, topk_idx = jax.lax.top_k(biased, k)
        topk_probs = jnp.take_along_axis(scores, topk_idx, axis=-1)
        if norm_topk:
            topk_probs = topk_probs / jnp.sum(
                topk_probs, axis=-1, keepdims=True
            )
        # The aux statistics want a distribution over the experts.
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        return probs, topk_probs, topk_idx
    if scoring != "softmax" or select_bias is not None:
        raise ValueError(
            f"scoring={scoring!r}: choose 'softmax' or 'sigmoid' (a "
            "selection bias goes with 'sigmoid')"
        )
    probs = jax.nn.softmax(router_logits, axis=-1)  # [G, E]

    sel_probs = probs
    if group_limit is not None:
        n_group, topk_group = group_limit
        if e % n_group:
            raise ValueError(
                f"group_limit: n_group={n_group} must divide E={e}"
            )
        per_group = e // n_group
        if k > topk_group * per_group:
            raise ValueError(
                f"group_limit: k={k} exceeds the {topk_group} surviving "
                f"groups' {topk_group * per_group} experts"
            )
        if topk_group < n_group:
            group_max = probs.reshape(g, n_group, per_group).max(-1)
            kth = jax.lax.top_k(group_max, topk_group)[0][..., -1:]
            keep = jnp.repeat(
                group_max >= kth, per_group, axis=-1
            )  # [G, E]
            # Masked-to-0 probs mirror HF's masked_fill(~mask, 0.0):
            # survivors keep their raw softmax mass as combine weights.
            sel_probs = jnp.where(keep, probs, 0.0)

    topk_probs, topk_idx = jax.lax.top_k(sel_probs, k)  # [G, k]
    if norm_topk:
        topk_probs = topk_probs / jnp.sum(
            topk_probs, axis=-1, keepdims=True
        )
    return probs, topk_probs, topk_idx


class SortedRoute(NamedTuple):
    """``sorted_route``'s assignments. ``route_topk_sorted`` hands on
    what ``ragged_dot`` over all k*G rows takes; ``eids`` and ``counts``
    are what it folds away: the assignments before the sentinel's are
    the live rows' held ones, sorted by expert, which is what
    ``tpufw.ops.moe_live`` is handed."""

    token: jax.Array  # [k*G] source token of each sorted assignment
    group_sizes: jax.Array  # [E] rows a group, the sentinel's in E-1
    gates: jax.Array  # [k*G] combine weight of each
    aux_lb: jax.Array
    z: jax.Array
    eids: jax.Array  # [k*G] its (local) expert id; the sentinel E last
    counts: jax.Array  # [E + 1] assignments a group, the sentinel's last


def route_topk_sorted(*args, **kwargs):
    """``sorted_route`` as ``ragged_dot`` takes it: (token [k*G],
    group_sizes [E], gates [k*G], aux_lb, z)."""
    return sorted_route(*args, **kwargs)[:5]


def sorted_route(
    router_logits: jax.Array,
    k: int,
    capacity: int,
    valid: Optional[jax.Array] = None,
    dtype=jnp.bfloat16,
    norm_topk: bool = True,
    group_limit: Optional[tuple[int, int]] = None,
    scoring: str = "softmax",
    select_bias: Optional[jax.Array] = None,
    held: Optional[tuple[int, int]] = None,
) -> SortedRoute:
    """Sorted-dispatch twin of ``route_topk_capacity``: identical
    selection, priority, capacity-drop, and aux-statistic semantics,
    but instead of materializing [G, E, C] one-hot dispatch/combine
    tensors it returns the k*G (token, expert) assignments SORTED by
    expert, ready for grouped expert matmuls (``jax.lax.ragged_dot``).
    The one-hot einsums cost O(G*E*C*d) FLOPs — measured 5x the expert
    matmuls themselves at bench scale (docs/PERF.md, r5 MoE section) —
    while the sorted path's gather/scatter is O(k*G*d) bytes.

    Capacity semantics match exactly: assignments beyond an expert's
    ``capacity`` (in the einsum path's priority order — expert slot 0
    of every token before slot 1, earlier tokens first) keep their
    sorted position but get a ZERO combine weight, so they contribute
    nothing (the residual stream carries the token), at the cost of
    computing the dropped rows. Invalid tokens (``valid`` False) sort
    LAST under a sentinel id E with zero weight, and ride in the last
    real group: their count is folded into ``group_sizes[E-1]``, so
    they multiply against expert E-1 (finite x a zero gate, exactly
    like dropped rows) and the expert weight stacks go to ragged_dot
    as they are stored. Expert E-1's real assignments sort before the
    sentinel rows, so their ranks and capacity drops are untouched.

    ``held = (first, n)``: this chip holds experts [first, first + n)
    of the E the router scores (its share of an expert-parallel
    layer). Selection and gate weights are over all E, as published;
    an assignment to an expert held elsewhere joins the sentinel group
    (zero gate: its part of the result is the other chip's to add),
    expert ids become local and ``group_sizes`` has length n, matching
    stacks of n experts. None = all E are here.

    Returns a ``SortedRoute``: ``token[i]`` is the source token id of
    the i-th SORTED assignment (gather ``x[token]`` to build the
    grouped input), ``group_sizes`` counts sorted rows per expert and
    always sums to k*G (every row of ragged_dot's output is defined),
    ``gates`` is the combine weight per sorted assignment; ``eids``
    and ``counts`` are the same assignments before the sentinel's were
    folded into group E-1.
    """
    g, e_all = router_logits.shape
    probs, topk_probs, topk_idx = _topk_select(
        router_logits, k, norm_topk, group_limit, scoring, select_bias
    )
    validf = None if valid is None else valid.reshape(g).astype(jnp.float32)

    # Slot-major flattening [k, G] reproduces the einsum path's
    # priority order under a stable sort: slot 0 of every token, then
    # slot 1, ties broken by token id.
    eids = topk_idx.T.reshape(k * g)  # [k*G]
    gates_flat = topk_probs.T.reshape(k * g)
    token = jnp.tile(jnp.arange(g, dtype=jnp.int32), k)
    e = e_all  # experts here; e is also the sentinel's id
    if held is not None:
        first, e = held
        local = eids - first
        eids = jnp.where((local >= 0) & (local < e), local, e)
    if validf is not None:
        invalid = validf < 0.5
        eids = jnp.where(invalid[token], e, eids)
        gates_flat = jnp.where(invalid[token], 0.0, gates_flat)

    order = jnp.argsort(eids, stable=True)
    sorted_eids = eids[order]
    counts = jnp.bincount(eids, length=e + 1).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts  # [E+1], sentinel last
    rank = jnp.arange(k * g, dtype=jnp.int32) - starts[sorted_eids]
    gates = jnp.where(
        (rank < capacity) & (sorted_eids < e), gates_flat[order], 0.0
    ).astype(dtype)
    # The sentinel rows ride in the last real group (zero gate).
    group_sizes = counts[:e].at[e - 1].add(counts[e])

    # Aux statistics: identical formulas to route_topk_capacity, on
    # the same valid-masked top-1 assignment mask.
    top1_mask = jax.nn.one_hot(topk_idx[:, 0], e_all, dtype=jnp.float32)
    if validf is not None:
        top1_mask = top1_mask * validf[:, None]
    aux_lb, z = _router_stats(router_logits, probs, top1_mask, validf, g)
    return SortedRoute(
        token[order], group_sizes, gates, aux_lb, z, sorted_eids, counts
    )


def _router_stats(router_logits, probs, top1_mask, validf, g):
    """Switch-style load-balance statistic + router z — ONE copy
    shared by both routing implementations (a drift here would change
    the training objective in only one path)."""
    if validf is None:
        n_valid = float(g)
        frac_tokens = jnp.sum(top1_mask, axis=0) / n_valid
        frac_probs = jnp.mean(probs, axis=0)
        z = jnp.mean(
            jnp.square(jax.scipy.special.logsumexp(router_logits, axis=-1))
        )
    else:
        n_valid = jnp.maximum(jnp.sum(validf), 1.0)
        frac_tokens = jnp.sum(top1_mask, axis=0) / n_valid
        frac_probs = jnp.sum(probs * validf[:, None], axis=0) / n_valid
        z = (
            jnp.sum(
                jnp.square(
                    jax.scipy.special.logsumexp(router_logits, axis=-1)
                )
                * validf
            )
            / n_valid
        )
    aux_lb = probs.shape[-1] * jnp.sum(frac_tokens * frac_probs)
    return aux_lb, z


def route_topk_capacity(
    router_logits: jax.Array,
    k: int,
    capacity: int,
    valid: Optional[jax.Array] = None,
    dtype=jnp.bfloat16,
    norm_topk: bool = True,
    group_limit: Optional[tuple[int, int]] = None,
    scoring: str = "softmax",
    select_bias: Optional[jax.Array] = None,
    held: Optional[tuple[int, int]] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Route G tokens to top-``k`` of E experts under a per-expert
    ``capacity``.

    Args:
      router_logits: [G, E] float32 router scores.
      k: experts per token.
      capacity: max tokens per expert (slots).
      valid: optional [G] bool/float — False rows (padding in packed
        batches) are excluded from routing, capacity, and the aux
        statistics so pads can't evict real tokens from experts.
      dtype: dtype of the returned dispatch/combine tensors (the
        activation dtype they will be contracted in).
      norm_topk: renormalize the selected top-k probabilities to sum to
        1 (Mixtral convention). False keeps the RAW softmax mass
        (DeepSeek-V2 ``norm_topk_prob=false`` — combine weights then
        sum to < 1 and the residual stream carries the rest).
      group_limit: optional ``(n_group, topk_group)`` — DeepSeek-V2
        236B "group_limited_greedy": experts partition into n_group
        contiguous groups, the topk_group groups with the highest
        per-group max score survive, and the top-k selection runs over
        the survivors only (HF modeling_deepseek_v2 DeepseekV2MoEGate).
        Aux statistics stay on the UNmasked distribution, matching the
        reference. Exact float ties between group maxima keep both
        groups (HF's torch.topk breaks such ties arbitrarily;
        measure-zero under real routers).
      scoring, select_bias, held: one semantics with
        ``route_topk_sorted``. With ``held = (first, n)`` the returned
        tensors are [G, n, C] over the experts held here; assignments
        to experts held elsewhere appear in neither.

    Returns:
      (dispatch [G, E, C], combine [G, E, C], aux_lb, z):
      ``dispatch`` is 0/1 token->slot assignment, ``combine`` is
      dispatch * renormalized top-k gate probability; ``aux_lb`` is the
      Switch-style load-balance statistic ``E * sum(frac_tokens *
      frac_probs)`` over top-1 assignments, ``z`` the mean squared
      router logsumexp — both raw (callers apply their config weights).
    """
    g, e_all = router_logits.shape
    probs, topk_probs, topk_idx = _topk_select(
        router_logits, k, norm_topk, group_limit, scoring, select_bias
    )
    validf = None if valid is None else valid.reshape(g).astype(jnp.float32)
    top1 = topk_idx[:, 0]
    e = e_all
    if held is not None:
        # Local ids; one_hot of an id outside [0, n) is all zeros, so
        # an expert held elsewhere gets no slot and no weight.
        first, e = held
        topk_idx = topk_idx - first

    # Priority order: expert slot 0 of every token beats slot 1, and
    # earlier tokens beat later ones — [k, G, E] cumsum order.
    mask = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32)  # [G, k, E]
    if validf is not None:
        mask = mask * validf[:, None, None]
    mask_kge = jnp.transpose(mask, (1, 0, 2)).reshape(k * g, e)
    pos_flat = jnp.cumsum(mask_kge, axis=0) - mask_kge  # pre-count
    pos = pos_flat.reshape(k, g, e).transpose(1, 0, 2)  # [G, k, E]
    within_cap = (pos < capacity) & (mask > 0)
    slot = jnp.sum(pos * mask, axis=-1)  # [G, k] slot per assignment
    dispatch = (
        jax.nn.one_hot(topk_idx, e, dtype=dtype)[..., None]
        * jax.nn.one_hot(slot.astype(jnp.int32), capacity, dtype=dtype)[
            :, :, None, :
        ]
        * jnp.any(within_cap, axis=-1, keepdims=True)[..., None].astype(dtype)
    )  # [G, k, E, C]
    if validf is not None:
        dispatch = dispatch * validf[:, None, None, None].astype(dtype)
    combine = dispatch * topk_probs[..., None, None].astype(dtype)
    dispatch = jnp.sum(dispatch, axis=1)  # [G, E, C]
    combine = jnp.sum(combine, axis=1)

    # Switch-transformer load-balance statistic over top-1 fractions,
    # computed over valid tokens only.
    if held is None:
        top1_mask = mask[:, 0, :]  # [G, E] (already zeroed on invalid)
    else:
        top1_mask = jax.nn.one_hot(top1, e_all, dtype=jnp.float32)
        if validf is not None:
            top1_mask = top1_mask * validf[:, None]
    aux_lb, z = _router_stats(router_logits, probs, top1_mask, validf, g)
    return dispatch, combine, aux_lb, z
