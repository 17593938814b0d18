"""Persistent device-resident KV slot pool for continuous batching.

The tick batcher in ``tpufw.workloads.serve`` coalesces waiting requests
into ONE ``generate`` scan: every row rides to the group's bucketed
``max_new``, EOS'd rows decode dead air, and arrivals wait a whole tick.
This module is the Orca/vLLM-style alternative at decode-STEP
granularity, mapped onto TPU static-shape discipline: the KV cache is a
pool of ``S`` slots with FIXED shapes (``[S, cache_len, heads, dim]``
leaves from the serving ``_cache_bucket`` ladder), and three jitted ops
move requests through it —

- ``insert``: copy one B=1 prefilled row cache into slot ``i`` with
  ``lax.dynamic_update_slice`` (the slot index is a TRACED scalar, so
  every slot shares one compiled program);
- ``decode_steps``: advance ALL slots ``k`` tokens in one device call
  (a ``lax.scan`` over the shared ``_decode_step``-style body) under
  per-slot ``(position, done, remaining)`` masks — occupancy is DATA,
  never a shape, so join/leave mid-flight can't recompile;
- ``retire``: freeze a slot's masks (error paths; natural completions
  are already frozen by the step body).

Per-slot cache cursors ride the flax "cache" collection as a ``[S]``
vector ``cache_index`` (trailing-slot-axis convention; the models'
store, ``tpufw.ops.kv_store.append``, branches on cursor rank).
``TRACE_COUNTS`` is bumped at TRACE time inside each op, so tests (and
operators) can assert the shape-stability contract: inserts/retires at
steady state add ZERO new traces.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from tpufw.infer.generate import _model_apply, _stream_prefill
from tpufw.infer.sampling import SamplingConfig, sample_token
from tpufw.ops import moe_live
from tpufw.ops.kv_store import (
    DECLINES, PAGE, RING, STATE, STATE_LEAVES, Decline, leaf_name, path_role,
    page_readers,
    ring_layers,
)

# Bumped INSIDE the jitted bodies, i.e. once per (re)trace, never per
# call: the cheap, version-proof way to assert "occupancy changes do
# not recompile" without reaching into jax internals.
TRACE_COUNTS: Dict[str, int] = {"insert": 0, "decode_steps": 0, "retire": 0}


def state_leaf_bytes(cache, kind: str = STATE) -> int:
    """Bytes of the per-slot leaves of ``kind`` in a cache pytree:
    recurrent STATE by default (0: keys and values only), a window
    layer's RING."""
    return sum(
        int(leaf.nbytes)
        for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
        if path_role(path).kind == kind
    )


def per_slot_decline(cache) -> Optional[Decline]:
    """Why a pool over ``cache`` shares no prefix page, exports no slot
    and verifies no block (None where it may): by the kind of per-slot
    leaf it holds that a page bundle does not carry and pages do not
    determine (tpufw.ops.kv_store ``Role.per_slot``, ``DECLINES``)."""
    for kind, decline in DECLINES.items():
        if state_leaf_bytes(cache, kind):
            return decline
    return None


def reject_state(pool, what: str) -> None:
    """The one refusal of what per-slot leaves make wrong today."""
    decline = getattr(pool, "per_slot", None)
    if decline is not None:
        raise ValueError(
            f"{what}: {type(pool.model).__name__} {decline.keeps}, so "
            f"this would continue from the wrong {decline.wrong}"
        )


def _track_seen(sampling: SamplingConfig) -> bool:
    return (
        sampling.repetition_penalty is not None
        and sampling.repetition_penalty != 1.0
    )


def live_segments(done, t: int):
    """Segment ids [S, t] of one pool step: 1 for a live row, 0 for a
    done or empty one. The store (tpufw.ops.kv_store) bounds the keys a
    step reads by the longest row that carries an id > 0, and a done
    row's cursor runs on to ``max_seq_len``; its output is pad either
    way."""
    return jnp.broadcast_to(
        jnp.where(done, 0, 1).astype(jnp.int32)[:, None], (done.shape[0], t)
    )


def pool_cache(model, params, n_slots: int) -> Tuple[Any, Tuple]:
    """Allocate a zeroed S-slot cache for ``model`` + its batch axes.

    Two ``eval_shape`` probes (B = S and B = S + 1) of the model's own
    cache init find, per leaf, the ONE axis that scales with batch —
    robust to scanned trunks (leading ``[L]`` stack), MLA latent caches,
    and any future cache layout. A leaf with NO batch axis is a cursor:
    it gets a trailing slot axis (``[] -> [S]``, ``[L] -> [L, S]``), so
    inside the model (after nn.scan slices the layer axis) the cursor
    arrives as the ``[B]`` vector the per-row attention branch expects.

    Zeros are safe initial state: never-written cache slots keep
    segment 0, and the segment mask hides them.
    """

    def shapes(b):
        def init(p):
            toks = jnp.zeros((b, 1), jnp.int32)
            pos = jnp.zeros((b, 1), jnp.int32)
            seg = jnp.ones((b, 1), jnp.int32)
            _, vars_ = model.apply(
                {"params": p}, toks, positions=pos, segment_ids=seg,
                mutable=["cache"],
            )
            return vars_["cache"]

        return jax.eval_shape(init, params)

    base = shapes(n_slots)
    probe = shapes(n_slots + 1)
    base_leaves, treedef = jax.tree_util.tree_flatten(base)
    probe_leaves = jax.tree_util.tree_leaves(probe)
    axes = []
    leaves = []
    for bl, pl in zip(base_leaves, probe_leaves):
        diff = [
            i for i, (x, y) in enumerate(zip(bl.shape, pl.shape)) if x != y
        ]
        if not diff:
            axes.append(None)
            leaves.append(jnp.zeros((*bl.shape, n_slots), bl.dtype))
        elif len(diff) == 1:
            axes.append(diff[0])
            leaves.append(jnp.zeros(bl.shape, bl.dtype))
        else:
            raise ValueError(
                "cache leaf with multiple batch-dependent axes "
                f"{bl.shape} vs {pl.shape} — slot pooling needs exactly "
                "one"
            )
    # Wrapped in the {"cache": ...} variables form the shared decode
    # apply closure (_model_apply) threads — same shape prefill hands
    # back, so insert's leaf zip lines up one-to-one.
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    return {"cache": tree}, tuple(axes)


@partial(
    jax.jit,
    static_argnames=("axes",),
    donate_argnames=("leaves", "token", "pos", "done", "remaining", "seen"),
)
def _insert_jit(
    leaves, row_leaves, slot, first, pos0, budget,
    token, pos, done, remaining, seen, row_seen, *, axes,
):
    """Copy a B=1 prefilled row into slot ``slot`` (traced scalar)."""
    TRACE_COUNTS["insert"] += 1
    out = []
    for leaf, row, axis in zip(leaves, row_leaves, axes):
        if axis is None:  # cursor leaf: trailing slot axis
            out.append(leaf.at[..., slot].set(row))
        else:
            start = tuple(
                slot if i == axis else 0 for i in range(leaf.ndim)
            )
            out.append(
                jax.lax.dynamic_update_slice(
                    leaf, row.astype(leaf.dtype), start
                )
            )
    token = token.at[slot].set(first)
    pos = pos.at[slot].set(pos0)
    done = done.at[slot].set(False)
    remaining = remaining.at[slot].set(budget)
    if seen is not None:
        seen = seen.at[slot].set(row_seen[0])
    return tuple(out), token, pos, done, remaining, seen


@partial(jax.jit, donate_argnames=("done", "remaining"))
def _retire_jit(done, remaining, slot):
    TRACE_COUNTS["retire"] += 1
    return done.at[slot].set(True), remaining.at[slot].set(0)


@partial(
    jax.jit,
    static_argnames=("model", "sampling", "pad_id", "eos_id"),
    donate_argnames=("cache", "token", "pos", "done", "remaining", "seen"),
)
def _decode_steps_jit(
    model, params, cache, token, pos, done, remaining, seen, keys,
    *, sampling, pad_id, eos_id,
):
    """Advance every slot ``len(keys)`` tokens in ONE device call.

    Mirrors ``generate``'s ``_decode_step`` body (sample -> seen update
    -> pad frozen rows -> eos) plus the per-slot ``remaining`` budget:
    a row emits its token THEN burns budget, so the EOS/boundary token
    itself is delivered and the row freezes after. Done rows keep
    stepping (static shapes; masking, not control flow) but feed pad
    back and emit pad out, and their cursors keep counting: they step
    with segment id 0, which is how the store leaves them out of the
    live length that bounds the keys it reads (``live_segments``).
    Per-slot state (kv_store role STATE) rides in
    ``cache`` and is updated in place by the model, a done row's too:
    it is junk from then on and the next insert overwrites all of it.
    """
    TRACE_COUNTS["decode_steps"] += 1
    apply = _model_apply(model, params)
    s = token.shape[0]
    track = _track_seen(sampling)

    def step(carry, rng_step):
        cache, token, pos, done, remaining, seen = carry
        logits, cache = apply(
            cache, token[:, None], pos[:, None], live_segments(done, 1)
        )
        nxt = sample_token(logits[:, -1, :], sampling, rng_step, seen)
        if track:
            seen = seen.at[jnp.arange(s), nxt].set(True)
        emitted = jnp.where(done, pad_id, nxt)
        remaining = jnp.where(done, remaining, remaining - 1)
        newly = remaining <= 0
        if eos_id is not None:
            newly = newly | (nxt == eos_id)
        done = done | newly
        return (cache, emitted, pos + 1, done, remaining, seen), emitted

    (cache, token, pos, done, remaining, seen), out = jax.lax.scan(
        step, (cache, token, pos, done, remaining, seen), keys
    )
    return cache, token, pos, done, remaining, seen, out.T  # [S, k]


def prefill_row(
    model,
    params,
    prompt,
    rng,
    *,
    sampling: SamplingConfig,
    eos_id: Optional[int],
    pad_to: Optional[int] = None,
    prefill_chunk_size: Optional[int] = None,
    pad_id: int = 0,
):
    """B=1 prefill for one request row, reusing ``_stream_prefill`` (the
    shared prefill + first-token discipline). ``pad_to`` left-pads the
    prompt to a bucketed static width so prefill programs are shared
    across lengths. Returns ``(row_cache, first_arr, first_int, done0,
    seen)`` — ``first_int`` is synced to host (the admission point is
    the scheduler's one natural sync; the next RoPE position is just
    ``len(prompt)``, no device read needed)."""
    p = len(prompt)
    width = max(pad_to or p, p)
    tokens = np.full((1, width), pad_id, np.int32)
    if p:
        tokens[0, width - p:] = np.asarray(prompt, np.int32)
    pads = np.full((1,), width - p, np.int32)
    cache, first, pos0, done, seen, _ = _stream_prefill(
        model,
        params,
        jnp.asarray(tokens),
        jnp.asarray(pads),
        rng,
        n_step_keys=1,
        sampling=sampling,
        eos_id=eos_id,
        prefill_chunk_size=prefill_chunk_size,
    )
    return cache, first, int(np.asarray(first)[0]), done, seen


@dataclasses.dataclass
class SlotPool:
    """Device state + jit plumbing for one (cache_len, sampling) pool.

    Host-side occupancy bookkeeping (which request owns which slot)
    lives in the scheduler; this object only carries the device arrays
    and re-binds them across the donated jit calls.
    """

    model: Any
    params: Any
    n_slots: int
    sampling: SamplingConfig
    pad_id: int
    eos_id: Optional[int]
    cache: Any
    axes: Tuple
    token: jax.Array
    pos: jax.Array
    done: jax.Array
    remaining: jax.Array
    seen: Any

    #: Called at the return of every program call made in here, with
    #: what was dispatched ("decode", "chunk", "insert", "row"): the
    #: device has work again. The serve scheduler mounts its pass
    #: ledger's ``fed`` after building the pool (no dataclass field).
    dispatched = staticmethod(lambda what: None)

    def __post_init__(self):
        # Pool state is born committed, where the weights live. To jit a
        # fresh uncommitted array and the committed output of an earlier
        # call are different arguments: left as created, every pool
        # program compiles a second time on its second call — after
        # warm-up, in the first live request (seen on the compile
        # counter, PR 21; TRACE_COUNTS cannot see it, nothing retraces).
        home = jax.tree_util.tree_leaves(self.params)[0].sharding
        if isinstance(home, NamedSharding):
            home = NamedSharding(home.mesh, PartitionSpec())
        self.home = home
        for name in ("cache", "token", "pos", "done", "remaining", "seen"):
            setattr(self, name, jax.device_put(getattr(self, name), home))
        #: Bytes of per-slot state (kv_store role STATE) this pool holds,
        #: and of its window layers' rings (role RING), all slots.
        self.state_bytes = state_leaf_bytes(self.cache)
        self.window_bytes = state_leaf_bytes(self.cache, RING)
        #: What those leaves make this pool decline (None: nothing).
        self.per_slot = per_slot_decline(self.cache)
        #: (window layers, ring slots a row), and the ring slots the pool
        #: holds: window layers x pool slots x window.
        self.ring_shape = ring_layers(self.cache)
        self.window_slots = self.ring_shape[0] * self.n_slots * self.ring_shape[1]
        #: Layers that read each page pair in a cached call, its writer
        #: included (``kv_store.page_readers``): 1 for most models.
        self.page_readers = page_readers(self.model.cfg)
        #: The PAGE leaves a cached call appends to, by name: what the
        #: store chooses its read by (``kv_store.in_place``).
        self.page_leaves = frozenset(
            leaf_name(path)
            for path, _ in jax.tree_util.tree_leaves_with_path(self.cache)
            if path_role(path).kind == PAGE
        )
        #: R of the routed experts' decode step (``moe_live.pool_rows``,
        #: the rule the program branches by: 0 where it never runs the
        #: kernel); None for a model without routed experts.
        widths = moe_live.expert_widths(self.params)
        self.expert_rows = None if widths is None else moe_live.pool_rows(
            self.model.cfg, self.n_slots, *widths
        )

    @classmethod
    def create(
        cls,
        model,
        params,
        n_slots: int,
        *,
        sampling: SamplingConfig = SamplingConfig(),
        pad_id: int = 0,
        eos_id: Optional[int] = None,
    ) -> "SlotPool":
        cache, axes = pool_cache(model, params, n_slots)
        seen = None
        if _track_seen(sampling):
            seen = jnp.zeros((n_slots, model.cfg.vocab_size), bool)
        return cls(
            model=model,
            params=params,
            n_slots=n_slots,
            sampling=sampling,
            pad_id=pad_id,
            eos_id=eos_id,
            cache=cache,
            axes=axes,
            token=jnp.zeros((n_slots,), jnp.int32),
            pos=jnp.zeros((n_slots,), jnp.int32),
            # Empty slots are born done with no budget: they emit pad
            # and their (zeroed, segment-0) cache rows stay invisible.
            done=jnp.ones((n_slots,), bool),
            remaining=jnp.zeros((n_slots,), jnp.int32),
            seen=seen,
        )

    @property
    def cache_len(self) -> int:
        return int(self.model.cfg.max_seq_len)

    def insert(self, slot: int, row_cache, first, pos0: int, budget: int,
               row_seen=None) -> None:
        """Occupy ``slot`` with a prefilled row. ``budget`` is the
        number of DECODE steps left (max_new - 1; the prefill-sampled
        first token is already out)."""
        leaves, treedef = jax.tree_util.tree_flatten(self.cache)
        row_leaves = jax.tree_util.tree_leaves(row_cache)
        perf = getattr(self, "perf", None)
        if perf is not None:
            # Cost harvest (tpufw.obs.perf; once per program): the
            # scheduler mounts ``pool.perf`` after _build_pool.
            perf.observe_jit(
                "serve_insert",
                _insert_jit,
                (
                    tuple(leaves), tuple(row_leaves), slot, first, pos0,
                    budget, self.token, self.pos, self.done,
                    self.remaining, self.seen, row_seen,
                ),
                kwargs=dict(axes=self.axes),
            )
        leaves, self.token, self.pos, self.done, self.remaining, \
            self.seen = _insert_jit(
                tuple(leaves), tuple(row_leaves), slot, first, pos0,
                budget, self.token, self.pos, self.done, self.remaining,
                self.seen, row_seen, axes=self.axes,
            )
        self.dispatched("insert")
        self.cache = jax.tree_util.tree_unflatten(treedef, list(leaves))

    def decode_steps(self, keys) -> jax.Array:
        """Advance all slots ``len(keys)`` tokens; returns [S, k]."""
        perf = getattr(self, "perf", None)
        if perf is not None:
            # One program per chunk-ladder rung (k is a shape).
            perf.observe_jit(
                f"serve_decode_k{len(keys)}",
                _decode_steps_jit,
                (
                    self.model, self.params, self.cache, self.token,
                    self.pos, self.done, self.remaining, self.seen, keys,
                ),
                kwargs=dict(
                    sampling=self.sampling, pad_id=self.pad_id,
                    eos_id=self.eos_id,
                ),
            )
        (
            self.cache, self.token, self.pos, self.done, self.remaining,
            self.seen, out,
        ) = _decode_steps_jit(
            self.model, self.params, self.cache, self.token, self.pos,
            self.done, self.remaining, self.seen, keys,
            sampling=self.sampling, pad_id=self.pad_id,
            eos_id=self.eos_id,
        )
        self.dispatched("decode")
        return out

    def spec_steps(self, proposals, key):
        """One self-draft speculative pass: verify host proposals
        [S, k] in a single t=k+1 target call and advance every slot by
        its per-slot accept count (tpufw.infer.speculative chunked
        path). Returns (out [S, k+1], n_emit [S], accept [S])."""
        from tpufw.infer import speculative as _spec

        reject_state(self, "speculative decoding")

        out = _spec.spec_verify_steps(self, proposals, key)
        self.dispatched("decode")
        return out

    def spec_draft_steps(self, draft_pool, key, k: int):
        """One fused draft+verify speculative pass against
        ``draft_pool`` (same slot count, cursors in lockstep).
        Returns (out [S, k+1], n_emit [S], accept [S])."""
        from tpufw.infer import speculative as _spec

        reject_state(self, "speculative decoding")
        reject_state(draft_pool, "speculative decoding (draft)")

        out = _spec.spec_draft_steps(self, draft_pool, key, k)
        self.dispatched("decode")
        return out

    def retire(self, slot: int) -> None:
        """Freeze ``slot`` (error paths — natural completions are
        already frozen by the step body's done/remaining masks)."""
        self.done, self.remaining = _retire_jit(
            self.done, self.remaining, slot
        )
