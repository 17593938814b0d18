"""Prefill / decode replica roles for disaggregated serving.

The split follows the workload physics (ROADMAP item 1 / Podracer's
decomposed-slice template): prefill is compute-bound and bursty,
decode is memory-bound and steady, so each gets its own mesh and its
own page arena. The handoff is PR 6's page arena made literal —

- :class:`PrefillEngine` runs admission (prefix-cache attach +
  ``_suffix_prefill_jit`` or cold ``prefill_row``) on its replica,
  scatters the row into its arena, then EXPORTS the slot's pages
  (int8 codes + page-structured scales raw) as a page bundle and
  releases the slot. Its prefix trie persists across requests, so
  shared prompts still prefill once per replica.
- :class:`DecodeEngine` imports bundles by allocating pages from its
  own arena and splicing them into its ``PagedSlotPool`` table. The
  cache shapes never change, so ``decode_steps`` stays the single
  jitted program it always was — migrations cost zero retraces, and
  greedy decode is bit-equal to a never-migrated run (the page table
  hides the physical ids).

RNG discipline mirrors the slot scheduler exactly: prefill stream
``fold_in(key(seed_base), job_index)``, chunk stream
``fold_in(key(seed_base + 1), chunk_index)`` — so a migrated request
draws the same sample stream the single-process path would.

``main_role`` is the container entrypoint behind
``TPUFW_SERVE_ROLE`` (deploy/manifests/13-serve-disagg-v5e8-jobset
.yaml): a framed-TCP server per engine, the router's HTTP front end
for the router role.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from tpufw.obs import events as obs_events
from tpufw.obs import reqtrace
from tpufw.obs import trace as obs_trace
from tpufw.serve import transport
from tpufw.serve.bundle import (
    BundleError,
    advertised_digests,
    attach_spill,
    decode_bundle,
    encode_bundle,
)
from tpufw.workloads.env import env_float, env_int, env_opt_str, env_str

DEFAULT_PEER_PORT = 8477


def _paged_models(model, page: int, kv_quant: str, arena_pages: int):
    """(pool_model, row_model) pair for a paged pool at the base
    model's full sequence budget — same construction the slot
    scheduler's ``_pool_model`` uses."""
    from tpufw.models import model_for_config

    cfg = model.cfg
    cache_len = int(cfg.max_seq_len)
    if page <= 0 or cache_len % page:
        raise ValueError(
            f"page={page} must be > 0 and divide max_seq_len={cache_len}"
        )
    pool_cfg = dataclasses.replace(
        cfg, kv_page=page, kv_pages=arena_pages, kv_quant=kv_quant
    )
    row_cfg = dataclasses.replace(cfg, kv_page=0, kv_quant="")
    return model_for_config(pool_cfg), model_for_config(row_cfg)


class _ChunkTicket:
    """One in-flight chunked prefill's place in the turn queue.
    Identity-compared on purpose (no ``__eq__``): two prompts with
    equal remaining work are still distinct tickets."""

    __slots__ = ("remaining", "seq", "blocked")

    def __init__(self, remaining: int, seq: int):
        self.remaining = remaining
        self.seq = seq
        #: set while this prefill is arena-stalled, so peers that CAN
        #: make progress aren't held behind it.
        self.blocked = False


def _fabric_signals(sig: Dict[str, Any], pool, spill) -> None:
    """KV-fabric occupancy/outcome numbers shared by both roles'
    ``signals()``: trie hit counters (the bench's hit-rate source) and
    spill-tier tier sizes + lifetime totals (the fleet deriver's spill
    occupancy series). Numeric-only on purpose — these ride into
    ``tpufw.obs.fleet``'s per-signal time series."""
    if pool.prefix is not None:
        sig["prefix_hits"] = pool.prefix_hits
        sig["prefix_misses"] = pool.prefix_misses
    if spill is not None:
        st = spill.stats()
        sig["spill_ram_pages"] = st["ram_pages"]
        sig["spill_dir_pages"] = st["dir_pages"]
        sig["spill_pages_total"] = st["spilled_pages_total"]
        sig["spill_restored_total"] = st["restored_total"]


class PrefillEngine:
    """One prefill replica: admission + prefix cache + page export.

    Slots are transient here — a slot lives exactly from insert to
    export+release — so the arena is sized for in-flight admissions
    plus whatever the prefix trie holds, not for decode residency."""

    def __init__(
        self,
        model,
        params,
        *,
        sampling,
        page: int,
        kv_quant: str = "",
        n_slots: int = 2,
        arena_pages: Optional[int] = None,
        eos_id: Optional[int] = None,
        seed_base: int = 0,
        prefix_cache: bool = True,
        prefill_chunk_pages: int = 0,
        spill=None,
        affinity_k: int = 0,
        events=None,
        tracer=None,
    ):
        from tpufw.infer.pages import PagedSlotPool

        cache_len = int(model.cfg.max_seq_len)
        per_row = cache_len // page
        pages = arena_pages or n_slots * per_row + 1
        pool_model, row_model = _paged_models(model, page, kv_quant, pages)
        self.pool = PagedSlotPool.create_paged(
            pool_model, row_model, params, n_slots,
            sampling=sampling, eos_id=eos_id,
            prefix_cache=prefix_cache,
        )
        self.page = page
        self.n_slots = n_slots
        self._eos = eos_id
        self._seed_base = seed_base
        self._job_index = 0
        self._events = events if events is not None else obs_events.NULL
        self._tracer = tracer if tracer is not None else obs_trace.NULL
        # KV fabric: host-RAM spill tier behind the trie (evicted
        # pages keep their KV; restore skips the chunk's re-prefill)
        # and the digest set the router's affinity steering reads.
        self._spill = spill
        self._affinity_k = max(0, int(affinity_k))
        self._digest_cache: Dict[str, Any] = {}
        if spill is not None:
            attach_spill(self.pool, spill, events=self._events)
        self._lock = threading.Lock()
        # Chunked mode: the engine lock is RELEASED between chunks, so
        # concurrent admissions interleave at chunk granularity instead
        # of serializing whole prompts (the lock wait that used to be
        # the "queue" stage collapses to one chunk's latency). The
        # condition variable wakes stalled chunk loops when a finalize
        # or an abandon returns pages.
        self.prefill_chunk_pages = max(0, int(prefill_chunk_pages))
        self._cv = threading.Condition(self._lock)
        #: pages promised to in-flight chunked admissions; admission
        #: blocks (rather than deadlocks) while the sum would pass the
        #: arena, so every admitted prefill can always finish.
        self._reserved = 0  # resource: counter reserved-pages
        #: Chunk-turn tickets, scheduled SRPT (shortest remaining
        #: prompt first, admission order on ties): equal-length
        #: prompts drain in strict FIFO — identical completion order
        #: to monolithic prefill — while a short prompt preempts a
        #: long one at the next chunk boundary instead of eating its
        #: whole remaining prefill as queue time. A bare lock gives
        #: neither property: the thread that just ran a chunk
        #: re-acquires before any waiter wakes.
        self._rr: List[_ChunkTicket] = []
        #: True while a chunk_step is in flight with the mutex
        #: RELEASED around its device call — exactly one chunk may
        #: compute at a time or the arena leaves would fork.
        self._chunk_busy = False
        self.prefill_inflight = 0  # resource: counter prefill-inflight
        self.prefill_chunks = 0
        self.prefill_resumes = 0
        self.migrations = 0
        self.migration_bytes = 0

    def signals(self) -> Dict[str, Any]:
        # wire: produces role-signals
        a = self.pool.allocator
        sig = {
            "role": "prefill",
            "pages_total": a.capacity,
            "pages_in_use": a.in_use,
            "migrations": self.migrations,
        }
        if self.prefill_chunk_pages:
            sig["prefill_chunk_pages"] = self.prefill_chunk_pages
            sig["prefill_inflight"] = self.prefill_inflight
            sig["prefill_chunks"] = self.prefill_chunks
        _fabric_signals(sig, self.pool, self._spill)
        if self._affinity_k:
            # wire: produces role-signals via prefix_digests
            sig["prefix_digests"] = advertised_digests(
                self.pool, self._spill, self._affinity_k,
                self._digest_cache,
            )
        return sig

    def prefill(
        self, prompt: Sequence[int], max_new: int, trace=None,
        session: Optional[str] = None,
    ) -> bytes:
        """Admit one request, export its slot as a page bundle, free
        the slot. Returns the serialized bundle (the first sampled
        token rides inside it as the ``token`` cursor). Raises
        ValueError when the row can never fit this arena.

        ``trace`` is an optional request-trace context (wire string or
        TraceContext); stage timings — queue (engine lock wait), admit
        (page grant + trie attach), compute, export — always ride in
        the bundle header, so the router can decompose its observed
        round trip even for untraced traffic."""
        # wire: produces trace-meta via tmeta, stages
        from tpufw.infer import slots as slots_mod

        import jax

        if self.prefill_chunk_pages:
            return self._prefill_chunked(
                prompt, max_new, trace, session=session
            )
        ctx = reqtrace.parse(trace)
        ctx = ctx.child() if ctx is not None else None
        prompt = list(prompt)
        need = len(prompt) + max_new - 1
        if self.pool.n_pages_for(need) > self.pool.allocator.capacity:
            raise ValueError(
                f"prompt+budget needs {self.pool.n_pages_for(need)} "
                f"pages; arena capacity is {self.pool.allocator.capacity}"
            )
        t_req = time.perf_counter()
        with self._lock:
            t_lock = time.perf_counter()
            queue_s = t_lock - t_req
            job_index = self._job_index
            self._job_index += 1
            rng = jax.random.fold_in(
                jax.random.key(self._seed_base), job_index
            )
            t0 = time.monotonic()
            grant = self.pool.acquire_pages(prompt, need)
            if grant is None:
                raise RuntimeError(
                    "prefill arena exhausted — in-flight admissions "
                    "plus trie-held pages left no room"
                )
            ids, shared_n = grant
            inserted = False
            slot = 0  # transient occupancy: insert -> export -> release
            try:
                t_admit = time.perf_counter()
                admit_s = t_admit - t_lock
                if shared_n:
                    cache, _f, first, _d, seen = (
                        self.pool.prefill_shared(
                            prompt, ids[:shared_n], rng
                        )
                    )
                else:
                    cache, _f, first, _d, seen = (
                        # tpulint: disable=TPU003 — exclusive if/else
                        # arms: exactly ONE of prefill_shared/
                        # prefill_row consumes this request's rng.
                        slots_mod.prefill_row(
                            self.pool.row_model, self.pool.params,
                            prompt, rng, sampling=self.pool.sampling,
                            eos_id=self._eos, pad_to=len(prompt),
                        )
                    )
                self.pool.insert_paged(
                    slot, cache, first, len(prompt), max_new - 1,
                    ids, shared_n, row_seen=seen,
                )
                inserted = True
                self.pool.register_prefix(prompt, ids)
                t_compute = time.perf_counter()
                compute_s = t_compute - t_admit
                state = self.pool.export_slot(slot)
            except BaseException:
                # The grant must not outlive a failed prefill/export
                # (TPU019): pre-insert the pages are still owned by
                # this frame, post-insert the transient slot owns
                # them — release whichever holder is live.
                if inserted:
                    self.pool.release_slot(slot)
                else:
                    self.pool.release_pages(ids)
                raise
            self.pool.release_slot(slot)
            export_s = time.perf_counter() - t_compute
            # Stage timings seal into the header BEFORE encode: the
            # encode+framing remainder shows up as the router-side
            # "wire" stage (rpc wall minus wall_s), by construction.
            stages = {
                "queue": round(queue_s, 6),
                "admit": round(admit_s, 6),
                "compute": round(compute_s, 6),
                "export": round(export_s, 6),
            }
            tmeta: Dict[str, Any] = {
                "stages": stages,
                "wall_s": round(
                    queue_s + admit_s + compute_s + export_s, 6
                ),
            }
            if ctx is not None:
                tmeta.update(ctx.meta())
            state["trace"] = tmeta
            # Ride the prompt ids in the header: a spec-enabled decode
            # replica mines its n-gram proposals from them. Optional,
            # so old decoders splice the bundle unchanged.
            state["prompt"] = [int(t) for t in prompt]
            if session:
                # Sticky session id stamped at prefill: the decode
                # side carries it through drain bundles so the router
                # can re-home the session by name.
                state["session"] = str(session)
            data = encode_bundle(state)
            self.migrations += 1
            self.migration_bytes += len(data)
            reqtrace.stage(
                self._tracer, ctx, "req_queue_wait", queue_s,
                role="prefill",
            )
            reqtrace.stage(
                self._tracer, ctx, "req_admit", admit_s,
                role="prefill", shared_pages=shared_n,
            )
            reqtrace.stage(
                self._tracer, ctx, "req_prefill_compute", compute_s,
                prompt_tokens=len(prompt),
            )
            reqtrace.stage(
                self._tracer, ctx, "req_page_export", export_s,
                pages=state["n_pages"],
            )
            fields = dict(
                pages=state["n_pages"], bytes=len(data),
                wall_s=round(time.monotonic() - t0, 6),
                direction="export", shared_pages=shared_n,
            )
            if ctx is not None:
                fields["trace"] = ctx.trace_id
            self._events.emit("serve_migration", **fields)
            return data

    def _turn(self) -> Optional[_ChunkTicket]:
        """The ticket whose chunk runs next: fewest pages left, then
        admission order. Arena-stalled tickets are skipped so a prompt
        whose next chunk fits isn't held behind one whose doesn't."""
        live = [t for t in self._rr if not t.blocked]
        if not live:
            return None
        return min(live, key=lambda t: (t.remaining, t.seq))

    @contextlib.contextmanager
    def _unlocked(self):
        """Release the engine mutex around a chunk's device call so
        admissions/abandons (host-only bookkeeping) never wait behind
        compute; ``_chunk_busy`` keeps the compute itself exclusive."""
        self._cv.release()
        try:
            yield
        finally:
            self._cv.acquire()

    def _prefill_chunked(
        self, prompt: Sequence[int], max_new: int, trace=None,
        session: Optional[str] = None,
    ) -> bytes:
        """Chunked admission: advance the prompt one page-aligned
        chunk per SRPT turn, with the engine mutex released both
        between chunks AND during each chunk's device call — so
        admission is immediate (host-only bookkeeping), concurrent
        prompts interleave at chunk granularity, and a short prompt
        preempts a long one at the next chunk boundary instead of
        head-of-line blocking behind it. The exported bundle carries prompt-only
        pages (``n_pages`` covers the prompt, not the decode budget —
        the decode replica allocates the tail from ``cache_index +
        remaining``), so the admission bound here is the prompt's page
        need alone: long prompts that used to 400 on prompt+budget now
        queue and drain chunk by chunk.

        Stage accounting stays additive: ``queue`` is the FIRST lock
        wait only, every later wait (lock re-acquires, arena stalls)
        lands in ``queue_chunks``, and ``wall_s`` is the literal sum —
        so the router's TTFT decomposition gains a
        ``prefill_queue_chunks`` term without losing additivity."""
        # wire: produces trace-meta via tmeta, stages
        import jax

        ctx = reqtrace.parse(trace)
        ctx = ctx.child() if ctx is not None else None
        prompt = list(prompt)
        n_prompt_pages = self.pool.n_pages_for(len(prompt))
        if n_prompt_pages > self.pool.allocator.capacity:
            raise ValueError(
                f"prompt needs {n_prompt_pages} pages; arena capacity "
                f"is {self.pool.allocator.capacity} (chunked bundles "
                "are prompt-only, so the decode budget no longer "
                "counts against this arena)"
            )
        t_req = time.perf_counter()
        deadline = time.monotonic() + 600.0
        with self._cv:
            t_lock = time.perf_counter()
            queue_s = t_lock - t_req
            # Admission-ordering guard: never promise more pages than
            # the arena holds, so every admitted prefill can finish
            # once its peers export. Blocks instead of deadlocking.
            # Deliberately does NOT wait out an in-flight chunk's
            # device call: start_chunked is host-only bookkeeping
            # (even the shared-prefix attach is deferred into the
            # first chunk_step's busy window), so admission slips in
            # mid-chunk — the door wait is lock + capacity, never
            # someone else's compute.
            while (
                self._reserved + n_prompt_pages
                > self.pool.allocator.capacity
            ):
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        "prefill arena oversubscribed — in-flight "
                        "chunked admissions never drained"
                    )
                self._cv.wait(0.25)
            job_index = self._job_index
            self._job_index += 1
            t0 = time.monotonic()
            # Raise-capable work (rng fold, start_chunked) runs AFTER
            # the reservation only under exception cover: a failure
            # here must hand back the counters it bumped, or the door
            # predicate above wedges every later admission (TPU019/
            # TPU021 — the queue-wait-leak bug class from PR 11).
            rng = jax.random.fold_in(
                jax.random.key(self._seed_base), job_index
            )
            self._reserved += n_prompt_pages
            self.prefill_inflight += 1
            cp = None
            try:
                cp = self.pool.start_chunked(
                    prompt, len(prompt), rng, self.prefill_chunk_pages
                )
                if cp.resumed:
                    self.prefill_resumes += 1
                admit_s = time.perf_counter() - t_lock
            except BaseException:
                # abandon_chunked may itself raise; the counter
                # restitution must survive that or the door predicate
                # wedges (TPU021).
                try:
                    if cp is not None:
                        self.pool.abandon_chunked(cp)
                finally:
                    self._reserved -= n_prompt_pages
                    self.prefill_inflight -= 1
                    self._cv.notify_all()
                raise
        chunk_w = max(1, self.prefill_chunk_pages) * self.pool.page
        token = None
        try:
            token = _ChunkTicket(
                remaining=-(-(len(prompt) - cp.cursor) // chunk_w),
                seq=job_index,
            )
            queue_chunks_s = 0.0
            compute_s = 0.0
            t_mark = time.perf_counter()
            with self._cv:
                self._rr.append(token)
                self._cv.notify_all()
            while True:
                with self._cv:
                    token.blocked = False
                    while self._chunk_busy or self._turn() is not token:
                        if time.monotonic() > deadline:
                            raise RuntimeError(
                                "prefill chunk turn starved — peers "
                                "never yielded the engine"
                            )
                        self._cv.wait(0.25)
                        token.blocked = False
                    t_got = time.perf_counter()
                    queue_chunks_s += t_got - t_mark
                    # The device call runs with the mutex RELEASED
                    # (see _unlocked); _chunk_busy keeps it exclusive
                    # while admissions slip in between.
                    self._chunk_busy = True
                    try:
                        status = self.pool.chunk_step(
                            cp, unlocked=self._unlocked
                        )
                    finally:
                        self._chunk_busy = False
                    token.remaining = -(
                        -(len(prompt) - cp.cursor) // chunk_w
                    )
                    if status == "stalled":
                        # Trie-held pages from peers' checkpoints own
                        # the arena right now; stand aside and wait
                        # for an export or an abandon to free some.
                        if time.monotonic() > deadline:
                            raise RuntimeError(
                                "prefill arena exhausted mid-chunk — "
                                "no peer freed pages in time"
                            )
                        token.blocked = True
                        self._cv.notify_all()
                        self._cv.wait(0.25)
                        t_mark = time.perf_counter()
                        continue
                    t_chunk = time.perf_counter()
                    compute_s += t_chunk - t_got
                    self.prefill_chunks += 1
                    self._events.emit(
                        "serve_prefill_chunk",
                        prompt_tokens=len(prompt), cursor=cp.cursor,
                        final=status == "done",
                        chunk_s=round(t_chunk - t_got, 6),
                    )
                    if status == "done":
                        slot = 0  # transient: finalize->export->release
                        self.pool.finalize_chunked(slot, cp, max_new - 1)
                        t_compute = time.perf_counter()
                        compute_s += t_compute - t_chunk
                        state = self.pool.export_slot(
                            slot, page_ids=cp.page_ids
                        )
                        self.pool.release_slot(slot)
                        # The slot owned (and just released) the pages;
                        # empty the cursor so a late failure's abandon
                        # can't double-release them.
                        cp.page_ids = []
                        export_s = time.perf_counter() - t_compute
                        # Done with chunk turns — free the head slot
                        # now so peers don't idle through the bundle
                        # encode below.
                        self._rr.remove(token)
                        self._cv.notify_all()
                        break
                    self._cv.notify_all()
                t_mark = time.perf_counter()
            stages = {
                "queue": round(queue_s, 6),
                "admit": round(admit_s, 6),
                "queue_chunks": round(queue_chunks_s, 6),
                "compute": round(compute_s, 6),
                "export": round(export_s, 6),
            }
            tmeta: Dict[str, Any] = {
                "stages": stages,
                "wall_s": round(
                    queue_s + admit_s + queue_chunks_s + compute_s
                    + export_s, 6
                ),
            }
            if ctx is not None:
                tmeta.update(ctx.meta())
            state["trace"] = tmeta
            state["prompt"] = [int(t) for t in prompt]
            if session:
                state["session"] = str(session)
            data = encode_bundle(state)
            self.migrations += 1
            self.migration_bytes += len(data)
            reqtrace.stage(
                self._tracer, ctx, "req_queue_wait", queue_s,
                role="prefill",
            )
            reqtrace.stage(
                self._tracer, ctx, "req_admit", admit_s,
                role="prefill", shared_pages=cp.shared_n,
            )
            reqtrace.stage(
                self._tracer, ctx, "req_queue_chunks", queue_chunks_s,
                role="prefill", chunks=cp.n_chunks,
            )
            reqtrace.stage(
                self._tracer, ctx, "req_prefill_compute", compute_s,
                prompt_tokens=len(prompt),
            )
            reqtrace.stage(
                self._tracer, ctx, "req_page_export", export_s,
                pages=state["n_pages"],
            )
            fields = dict(
                pages=state["n_pages"], bytes=len(data),
                wall_s=round(time.monotonic() - t0, 6),
                direction="export", shared_pages=cp.shared_n,
            )
            if ctx is not None:
                fields["trace"] = ctx.trace_id
            self._events.emit("serve_migration", **fields)
            return data
        except BaseException:
            with self._cv:
                # Abandon keeps trie-checkpointed full pages held:
                # a re-submitted identical prompt resumes from the
                # last completed page instead of restarting.
                self.pool.abandon_chunked(cp)
            raise
        finally:
            with self._cv:
                # Counters first: nothing before them may raise, or a
                # failed ticket teardown would wedge the door
                # predicate forever (TPU021).
                self._reserved -= n_prompt_pages
                self.prefill_inflight -= 1
                if token is not None and token in self._rr:
                    # failure paths still hold a queue ticket
                    self._rr.remove(token)
                self._cv.notify_all()


class DecodeEngine:
    """One decode replica: bundle import + continuous chunked decode.

    ``submit`` splices a bundle into a free slot; ``collect`` drives
    shared decode chunks (all active slots advance together — the
    same continuous-batching math as the slot scheduler) until that
    slot's budget is spent, then frees its pages."""

    def __init__(
        self,
        model,
        params,
        *,
        sampling,
        page: int,
        kv_quant: str = "",
        n_slots: int = 4,
        arena_pages: Optional[int] = None,
        eos_id: Optional[int] = None,
        seed_base: int = 0,
        chunk: int = 4,
        spec_k: int = 0,
        spec_min_accept: float = 0.25,
        prefill_chunk_pages: int = 0,
        piggyback: float = 0.0,
        spill=None,
        affinity_k: int = 0,
        events=None,
        tracer=None,
    ):
        from tpufw.infer.pages import PagedSlotPool

        cache_len = int(model.cfg.max_seq_len)
        per_row = cache_len // page
        pages = arena_pages or n_slots * per_row + 1
        pool_model, row_model = _paged_models(model, page, kv_quant, pages)
        # Prefix trie on the decode side ONLY with piggyback prefill
        # enabled: the splice path never trie-registers (a hold would
        # pin migrated pages past their row), but piggybacked chunked
        # prefills checkpoint into the trie exactly like a prefill
        # replica's — which is what the router's prefix-affinity
        # steering keys on at the decode pool.
        piggy = bool(
            max(0, int(prefill_chunk_pages)) and float(piggyback) > 0
        )
        self.pool = PagedSlotPool.create_paged(
            pool_model, row_model, params, n_slots,
            sampling=sampling, eos_id=eos_id, prefix_cache=piggy,
        )
        self.page = page
        self.n_slots = n_slots
        self.chunk = max(1, chunk)
        self._eos = eos_id
        self._seed_base = seed_base
        self._chunk_index = 0
        self._job_index = 0
        # Prefill/decode fungibility: with a chunk size and a spare-
        # capacity waterline set, this replica accepts RAW prompts
        # (no prefill hop, no bundle) and prefills them chunk-by-chunk
        # inside the same passes that advance its decode slots — the
        # router's piggyback path under prefill-side load skew.
        self.prefill_chunk_pages = max(0, int(prefill_chunk_pages))
        self.piggyback = max(0.0, float(piggyback))
        self._events = events if events is not None else obs_events.NULL
        self._tracer = tracer if tracer is not None else obs_trace.NULL
        # KV fabric: spill tier (trie pages under piggyback, session
        # bundles at drain — "session" entries persist to the shared
        # directory the router re-homes from), affinity digests, and
        # the drain latch that turns scale-in into migration.
        self._spill = spill
        self._affinity_k = max(0, int(affinity_k))
        self._digest_cache: Dict[str, Any] = {}
        if spill is not None:
            attach_spill(self.pool, spill, events=self._events)
        self._draining = False
        # Set (lock-free, atomic attribute write) by drain() BEFORE it
        # contends for ``_cv``: the collect loop holds the lock across
        # chunks, so without a yield point the drain could only latch
        # in the submit->collect gap. The loop checks this flag at
        # every chunk boundary and waits the lock away so the export
        # sees the slots live.
        self._drain_pending = False
        self.sessions_drained = 0
        self.sessions_resumed = 0
        # Speculative self-drafting (n-gram proposals against the
        # request's own history, verified by spec_steps' single
        # jitted pass). No draft model on a replica — the monolithic
        # scheduler owns that path; here speculation must cost zero
        # extra HBM so migration parity stays trivial.
        self.spec_k = max(0, int(spec_k))
        self._ema = None
        self.spec_passes = 0
        if self.spec_k:
            from tpufw.infer.speculative import AcceptEMA

            if self.spec_k + 1 > page:
                raise ValueError(
                    f"spec_k={self.spec_k} needs spec_k+1 <= page="
                    f"{page} (verify writes one block per pass)"
                )
            rp = getattr(sampling, "repetition_penalty", None)
            if rp is not None and rp != 1.0:
                # Acceptance at position j changes the penalized
                # distribution at j+1 — speculation can't honour the
                # penalty, so this replica runs plain chunks.
                self._events.emit(
                    "serve_spec", level="warn", k=self.spec_k,
                    mode="plain_fallback", reason="repetition_penalty",
                )
                self.spec_k = 0
            else:
                self._ema = AcceptEMA(
                    n_slots, min_accept=spec_min_accept,
                )
        self._cv = threading.Condition()
        #: slot -> {"tokens": [...], "budget": int, "done": bool} plus
        #: the reqtrace bookkeeping collect_ex reports (splice_s,
        #: first_flush_s, n_chunks, ctx).
        self._jobs: Dict[int, Dict[str, Any]] = {}
        self.migrations = 0
        self.migration_bytes = 0

    # ---- router signals -------------------------------------------

    def signals(self) -> Dict[str, Any]:
        # wire: produces role-signals
        a = self.pool.allocator
        with self._cv:
            active = len(self._jobs)
            inflight = sum(
                1 for j in self._jobs.values()
                if j.get("cp") is not None
            )
        sig = {
            "role": "decode",
            "pages_total": a.capacity,
            "pages_in_use": a.in_use,
            "slots_total": self.n_slots,
            "slots_active": active,
            "migrations": self.migrations,
        }
        if self.spec_k:
            sig["spec_k"] = self.spec_k
            sig["spec_passes"] = self.spec_passes
        if self.prefill_chunk_pages and self.piggyback:
            sig["prefill_chunk_pages"] = self.prefill_chunk_pages
            sig["piggyback_waterline"] = self.piggyback
            sig["prefill_inflight"] = inflight
        # Draining rides the signals so the router stops steering new
        # work here the moment the drain latch flips (the reprobe after
        # a failed decode reads this too).
        sig["draining"] = 1 if self._draining else 0
        if self.sessions_drained or self.sessions_resumed:
            sig["sessions_drained"] = self.sessions_drained
            sig["sessions_resumed"] = self.sessions_resumed
        _fabric_signals(sig, self.pool, self._spill)
        if self._affinity_k and self.pool.prefix is not None:
            # wire: produces role-signals via prefix_digests
            sig["prefix_digests"] = advertised_digests(
                self.pool, self._spill, self._affinity_k,
                self._digest_cache,
            )
        return sig

    def can_accept(self, n_pages: int) -> bool:
        with self._cv:
            if self._draining or len(self._jobs) >= self.n_slots:
                return False
            deficit = self._cp_deficit_locked()
        return n_pages + deficit <= self.pool.allocator.n_free

    def _cp_deficit_locked(self) -> int:
        """Pages still owed to in-flight piggyback prefills (caller
        holds ``_cv``). Admissions that would eat into this sum are
        refused — the chunked rows must always be able to finish."""
        return sum(
            j["cp"].deficit for j in self._jobs.values()
            if j.get("cp") is not None
        )

    def can_piggyback(self, n_pages: int) -> bool:
        """Would ``submit_raw`` accept a raw prompt needing
        ``n_pages`` right now? Mirrors its admission test: pages must
        FIT (hard feasibility — this row plus every in-flight chunked
        deficit inside the arena), and the pool's idle-slot fraction
        must clear the ``piggyback`` waterline. Slots, not pages, are
        the waterline currency: a decode pass computes every slot row
        whether occupied or not, so "spare chunk capacity" IS idle
        slots — a mostly-empty arena on a fully-busy pool has no spare
        compute to scavenge."""
        if not (self.prefill_chunk_pages and self.piggyback):
            return False
        a = self.pool.allocator
        with self._cv:
            n_jobs = len(self._jobs)
            if self._draining or n_jobs >= self.n_slots:
                return False
            deficit = self._cp_deficit_locked()
        return (
            a.n_free - deficit - n_pages >= 0
            and self.n_slots - n_jobs
            >= self.piggyback * self.n_slots
        )

    # ---- bundle import --------------------------------------------

    def submit(self, data: bytes) -> int:
        """Import a serialized bundle; returns the slot handle for
        ``collect``. BundleError/ValueError mean the bundle was
        rejected with the arena untouched."""
        # wire: consumes bundle-header via state
        t0 = time.monotonic()
        t0p = time.perf_counter()
        state = decode_bundle(data)
        ctx = reqtrace.parse(state.get("trace"))
        ctx = ctx.child() if ctx is not None else None
        # Resumed session bundle (drain export): seed the emitted list
        # so the client receives one continuous sequence, and lift the
        # budget by the tokens already emitted so the budget_left math
        # (budget - (len(tokens) - 1)) lands exactly at the origin
        # replica's remaining count — zero-divergence resumption.
        emitted = state.get("tokens")
        resumed = isinstance(emitted, list) and len(emitted) > 0
        if resumed:
            tokens0 = [int(t) for t in emitted]
            budget0 = int(state["remaining"]) + len(tokens0) - 1
        else:
            tokens0 = [int(state["token"])]
            budget0 = int(state["remaining"])
        with self._cv:
            if self._draining:
                raise RuntimeError(
                    "decode replica draining — no new admissions"
                )
            free = [
                s for s in range(self.n_slots) if s not in self._jobs
            ]
            if not free:
                raise RuntimeError("decode replica: no free slot")
            slot = free[0]
            # Chunked prefill engines export prompt-only bundles
            # (n_pages covers the prompt, not the decode budget): the
            # decode side owns the residency decision, so size the
            # grant for the row's full life. Monolithic bundles
            # already carry their budget pages — the max is a no-op.
            n_alloc = max(
                int(state["n_pages"]),
                self.pool.n_pages_for(
                    int(state["cache_index"]) + int(state["remaining"])
                ),
            )
            deficit = self._cp_deficit_locked()
            if deficit and self.pool.allocator.n_free - n_alloc < deficit:
                raise RuntimeError(
                    "decode replica: bundle would starve an in-flight "
                    f"piggyback prefill ({n_alloc} pages wanted, "
                    f"{deficit} owed, {self.pool.allocator.n_free} free)"
                )
            ids = self.pool.allocator.alloc(n_alloc)
            if ids is None:
                raise RuntimeError(
                    "decode replica: arena cannot fit the bundle "
                    f"({n_alloc} pages, "
                    f"{self.pool.allocator.n_free} free)"
                )
            try:
                self.pool.splice_slot(slot, state, ids)
            except Exception:
                self.pool.allocator.release(ids)
                raise
            splice_s = time.perf_counter() - t0p
            job = {
                "tokens": tokens0,
                "budget": budget0,
                "done": bool(state["done"])
                or int(state["remaining"]) <= 0,
                # Prompt ids when the producer shipped them (optional
                # header field): the n-gram self-draft mines proposals
                # from prompt + generated history.
                "history": [
                    int(t) for t in (state.get("prompt") or [])
                ],
                # Sticky session id (optional header field): drain
                # exports this slot under it so the router can re-home.
                "session": state.get("session") or None,
                "ctx": ctx,
                "splice_s": splice_s,
                # perf_counter at splice end: first_flush measures
                # from here to the first decode-chunk extension.
                "t_ready": time.perf_counter(),
                "first_flush_s": None,
                "n_chunks": 0,
            }
            self._jobs[slot] = job
            if self._ema is not None and not job["done"]:
                self._ema.occupy(slot)
            if job["done"]:
                # Prefill already finished this request (EOS as the
                # first sampled token, or a zero budget): no decode
                # chunk will ever retire the slot, so free its pages
                # here or they leak until the arena saturates.
                self.pool.release_slot(slot)
                # The first (and only) token arrived inside the
                # bundle — it is flushed the moment the splice lands.
                job["first_flush_s"] = 0.0
            if resumed:
                self.sessions_resumed += 1
            self.migrations += 1
            self.migration_bytes += len(data)
            self._cv.notify_all()
        reqtrace.stage(
            self._tracer, ctx, "req_splice", splice_s,
            pages=int(state["n_pages"]), slot=slot,
        )
        fields = dict(
            pages=int(state["n_pages"]), bytes=len(data),
            wall_s=round(time.monotonic() - t0, 6),
            direction="import",
        )
        if ctx is not None:
            fields["trace"] = ctx.trace_id
        self._events.emit("serve_migration", **fields)
        return slot

    def submit_raw(
        self, prompt: Sequence[int], max_new: int, trace=None,
        session: Optional[str] = None,
    ) -> int:
        """Piggyback admission: accept a RAW prompt — no prefill hop,
        no bundle migration — and prefill it chunk-by-chunk inside the
        same passes that advance the resident decode slots. Admission
        requires the pool's idle-slot fraction to clear the
        ``piggyback`` waterline AND the arena to fit this row's full
        page need on top of every in-flight piggyback deficit, so
        resident decodes keep headroom and chunked rows can always
        finish. Raises RuntimeError when the waterline (or a free
        slot, or the pages) is missing; the router falls back to the
        dedicated-prefill path."""
        # wire: consumes control-frame via prompt
        import jax

        if not (self.prefill_chunk_pages and self.piggyback):
            raise RuntimeError(
                "piggyback admission disabled — needs both "
                "TPUFW_SERVE_PREFILL_CHUNK and TPUFW_SERVE_PIGGYBACK"
            )
        ctx = reqtrace.parse(trace)
        ctx = ctx.child() if ctx is not None else None
        prompt = [int(t) for t in prompt]
        need = len(prompt) + max_new - 1
        n_total = self.pool.n_pages_for(need)
        a = self.pool.allocator
        if n_total > a.capacity:
            raise ValueError(
                f"prompt+budget needs {n_total} pages; arena "
                f"capacity is {a.capacity}"
            )
        with self._cv:
            if self._draining:
                raise RuntimeError(
                    "decode replica draining — no new admissions"
                )
            free = [
                s for s in range(self.n_slots) if s not in self._jobs
            ]
            if not free:
                raise RuntimeError("decode replica: no free slot")
            deficit = self._cp_deficit_locked()
            if a.n_free - deficit - n_total < 0:
                raise RuntimeError(
                    "decode replica: arena cannot seat the row — "
                    f"{a.n_free} free minus {deficit} owed leaves "
                    f"less than the {n_total} pages wanted"
                )
            if (
                self.n_slots - len(self._jobs)
                < self.piggyback * self.n_slots
            ):
                raise RuntimeError(
                    "decode replica: piggyback waterline — "
                    f"{self.n_slots - len(self._jobs)} idle of "
                    f"{self.n_slots} slots clears less than "
                    f"{self.piggyback:.0%}"
                )
            slot = free[0]
            job_index = self._job_index
            self._job_index += 1
            # Same stream a dedicated prefill replica would draw, so a
            # piggybacked request samples identically to a migrated one.
            rng = jax.random.fold_in(
                jax.random.key(self._seed_base), job_index
            )
            cp = self.pool.start_chunked(
                prompt, need, rng, self.prefill_chunk_pages
            )
            self._jobs[slot] = {  # resource: transfers pages
                "tokens": [],
                "budget": max_new - 1,
                "done": False,
                "history": list(prompt),
                "session": str(session) if session else None,
                "ctx": ctx,
                "splice_s": 0.0,
                "t_ready": time.perf_counter(),
                "first_flush_s": None,
                "n_chunks": 0,
                "cp": cp,
                "prefill_s": 0.0,
                "prefill_queue_s": 0.0,
                "prefill_chunks": 0,
            }
            self._cv.notify_all()
        reqtrace.stage(
            self._tracer, ctx, "req_piggyback_admit", 0.0,
            slot=slot, pages=n_total,
        )
        return slot

    # ---- drain (scale-in / SIGTERM) -------------------------------

    def drain(self) -> Dict[str, Any]:
        """Turn scale-down from "drop sessions" into "migrate them":
        latch the drain flag (admissions start refusing), export every
        live session's slot as a spill bundle to the session store
        (``SpillTier`` persists kind "session" to the shared
        directory), release the slots, and mark the jobs drained so
        in-flight ``collect_ex`` calls return immediately with the
        ``drained`` flag. The router re-homes each sticky session onto
        a surviving replica, which restores through the normal splice
        path — zero token divergence under greedy decode (the engine
        default). Sessions mid-piggyback-prefill (no slot yet) and
        sessionless jobs have nothing to resume; their partial work is
        dropped and the caller sees a plain drained reply. Idempotent:
        a second drain finds no live jobs."""
        # wire: produces session-bundle via spill-tier
        t0 = time.monotonic()
        exported: List[str] = []
        dropped = 0
        # Ask the chunk-driving collector (which holds _cv across
        # device calls) to yield at its next chunk boundary — without
        # this the drain only ever latches between requests.
        self._drain_pending = True
        with self._cv:
            self._drain_pending = False
            self._draining = True
            for slot, job in list(self._jobs.items()):
                if job["done"]:
                    continue
                session = job.get("session")
                cp = job.get("cp")
                if cp is not None:
                    # resource: releases pages
                    self.pool.abandon_chunked(cp)
                    job["cp"] = None
                    dropped += 1
                elif session and self._spill is not None:
                    # Export BEFORE release: after release the table
                    # row is zeroed and the pages may be reassigned.
                    state = self.pool.export_slot(slot)
                    state["session"] = str(session)
                    state["tokens"] = [int(t) for t in job["tokens"]]
                    if job.get("history"):
                        state["prompt"] = [
                            int(t) for t in job["history"]
                        ]
                    data = encode_bundle(state)
                    self._spill.put(
                        "session", str(session), data,
                        int(state["n_pages"]),
                    )
                    self.pool.release_slot(slot)
                    if self._ema is not None:
                        self._ema.vacate(slot)
                    self.sessions_drained += 1
                    exported.append(str(session))
                else:
                    self.pool.release_slot(slot)
                    if self._ema is not None:
                        self._ema.vacate(slot)
                    dropped += 1
                job["done"] = True
                job["drained"] = True
            self._cv.notify_all()
        self._events.emit(
            "serve_spill", entry="session", direction="out",
            sessions=len(exported), dropped=dropped,
            wall_s=round(time.monotonic() - t0, 6),
        )
        return {
            "drained": True, "sessions": exported, "dropped": dropped,
        }

    # ---- decode loop ----------------------------------------------

    def _run_prefill_chunks_locked(self) -> bool:
        """Advance every piggybacked prefill by one page-aligned chunk
        (caller holds ``_cv``). A finished prefill finalizes into its
        slot and joins the next decode pass — mixed prefill+decode
        pools, no separate tick. Returns whether any chunk ran."""
        progressed = False
        for slot, job in list(self._jobs.items()):
            cp = job.get("cp")
            if cp is None or job["done"]:
                continue
            t0 = time.perf_counter()
            status = self.pool.chunk_step(cp)
            if status == "stalled":
                continue  # retry after a peer frees pages
            dt = time.perf_counter() - t0
            progressed = True
            job["prefill_s"] += dt
            job["prefill_chunks"] += 1
            self._events.emit(
                "serve_prefill_chunk",
                prompt_tokens=len(cp.prompt), cursor=cp.cursor,
                final=status == "done", chunk_s=round(dt, 6),
                slot=slot,
            )
            if status != "done":
                continue
            job["cp"] = None
            job["tokens"] = [cp.first_int]
            t1 = time.perf_counter()
            job["prefill_queue_s"] = max(
                0.0, (t1 - job["t_ready"]) - job["prefill_s"]
            )
            job["first_flush_s"] = t1 - job["t_ready"]
            reqtrace.stage(
                self._tracer, job["ctx"], "req_first_token",
                job["first_flush_s"], slot=slot,
            )
            if cp.done0 or job["budget"] <= 0:
                # EOS as the first sampled token (or a zero budget):
                # complete before ever owning a pool slot, so the
                # pages go straight back — no trie here, abandon
                # frees everything.
                job["done"] = True
                self.pool.abandon_chunked(cp)
            else:
                self.pool.finalize_chunked(slot, cp, job["budget"])
                if self._ema is not None:
                    self._ema.occupy(slot)
        return progressed

    def _run_chunk_locked(self) -> None:
        """One shared decode chunk (caller holds ``_cv``). Every
        active slot advances; retired slots free their pages.

        With ``spec_k`` set the pass may run speculatively: n-gram
        proposals from each slot's history, verified in ONE target
        call, per-slot advance = its own accept count (+1 bonus).
        The acceptance EMA decides spec-vs-plain per pass, so
        low-yield traffic degrades to plain chunks and periodically
        re-probes — a migrated request decodes bit-equal either way
        (greedy verify is exact)."""
        import jax
        import numpy as np

        progressed = self._run_prefill_chunks_locked()
        live = {
            s: j for s, j in self._jobs.items()
            if not j["done"] and j.get("cp") is None
        }
        if not live:
            if not progressed and any(
                j.get("cp") is not None for j in self._jobs.values()
            ):
                # Every piggyback prefill is stalled on pages and no
                # decode slot is live to free any: sleep on the
                # condition instead of spinning until a release lands.
                # tpulint: disable=TPU020 — deliberate timed backoff,
                # not a predicate wait: the caller's collect loop IS
                # the enclosing retry loop, and a spurious wakeup just
                # re-polls the stall condition one tick early.
                self._cv.wait(0.001)
            return
        use_spec = self._ema is not None and self._ema.use_spec(
            sorted(live)
        )
        k = self.spec_k if use_spec else self.chunk
        t0 = time.perf_counter()
        key = jax.random.fold_in(
            jax.random.key(self._seed_base + 1), self._chunk_index
        )
        chunk_index = self._chunk_index
        self._chunk_index += 1
        n_emit = accept = None
        if use_spec:
            from tpufw.infer import speculative as spec_mod

            props = np.zeros((self.n_slots, k), np.int32)
            for slot, job in live.items():
                props[slot] = spec_mod.ngram_propose(
                    job["history"] + job["tokens"], k
                )
            out, n_emit, accept = self.pool.spec_steps(props, key)
            out = np.asarray(out)
            n_emit = np.asarray(n_emit)
            accept = np.asarray(accept)
        else:
            out = np.asarray(
                # tpulint: disable=TPU003 — exclusive if/else arms:
                # exactly ONE of spec_steps/decode_steps consumes this
                # chunk's key.
                self.pool.decode_steps(jax.random.split(key, k))
            )
        t1 = time.perf_counter()
        chunk_s = t1 - t0
        accept_frac = 0.0
        for slot, job in live.items():
            budget_left = job["budget"] - (len(job["tokens"]) - 1)
            if use_spec:
                take = min(int(n_emit[slot]), budget_left)
                row = out[slot, :take].tolist()
                self._ema.update(slot, int(accept[slot]) / k)
                accept_frac += int(accept[slot]) / k
            else:
                row = out[slot].tolist()[: min(k, budget_left)]
            if self._eos is not None and self._eos in row:
                row = row[: row.index(self._eos) + 1]
            job["tokens"].extend(row)
            job["n_chunks"] += 1
            if row and job["first_flush_s"] is None:
                # First decode tokens for this request just became
                # host-visible: the splice->flush gap is the decode
                # side's contribution to TTFT beyond the first
                # (bundled) token.
                job["first_flush_s"] = t1 - job["t_ready"]
                reqtrace.stage(
                    self._tracer, job["ctx"], "req_first_token",
                    job["first_flush_s"], slot=slot,
                )
            reqtrace.stage(
                self._tracer, job["ctx"], "req_decode_chunk", chunk_s,
                slot=slot, chunk_index=chunk_index,
                new_tokens=len(row),
            )
            if (
                len(job["tokens"]) - 1 >= job["budget"]
                or (self._eos is not None and row
                    and row[-1] == self._eos)
            ):
                job["done"] = True
                self.pool.release_slot(slot)
                if self._ema is not None:
                    self._ema.vacate(slot)
        if use_spec:
            self.spec_passes += 1
            self._events.emit(
                "serve_spec", k=k, mode="pass", rows=len(live),
                accept_rate=round(accept_frac / len(live), 4),
            )
        self._cv.notify_all()

    def collect(self, slot: int, timeout: float = 600.0) -> List[int]:
        """Block until ``slot``'s request completes; returns its full
        token list (first token included). Exactly one caller drives
        chunks at a time; other waiters sleep on the condition."""
        return self.collect_ex(slot, timeout)["tokens"]

    def collect_ex(
        self, slot: int, timeout: float = 600.0
    ) -> Dict[str, Any]:
        """``collect`` plus the decode-side stage timings the router
        folds into the request's TTFT decomposition: ``splice_s``
        (bundle parse + page alloc + splice), ``first_flush_s``
        (splice end -> first decode-chunk flush; 0.0 when the bundled
        token already finished the request), ``n_chunks``."""
        # wire: produces decode-reply
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                job = self._jobs.get(slot)
                if job is None:
                    raise KeyError(f"no active job in slot {slot}")
                if job["done"]:
                    del self._jobs[slot]
                    out = {
                        "tokens": job["tokens"],
                        "splice_s": round(job["splice_s"], 6),
                        "first_flush_s": round(
                            job["first_flush_s"] or 0.0, 6
                        ),
                        "n_chunks": job["n_chunks"],
                    }
                    if "prefill_chunks" in job:
                        # Piggybacked request: the replica did its
                        # prefill too — stage timings for the
                        # router's TTFT decomposition.
                        out["piggyback"] = True
                        out["prefill_s"] = round(job["prefill_s"], 6)
                        out["prefill_queue_s"] = round(
                            job["prefill_queue_s"], 6
                        )
                        out["prefill_chunks"] = job["prefill_chunks"]
                    if job.get("drained"):
                        # The replica drained mid-request: the reply
                        # carries the drained flag (+ session id when
                        # resumable) so the router re-homes instead of
                        # returning a truncated generation.
                        out["drained"] = True
                        if job.get("session"):
                            out["session"] = job["session"]
                    return out
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"slot {slot} did not finish in {timeout}s"
                    )
                if self._drain_pending:
                    # A drain is blocked on this lock: yield it for a
                    # beat so the export runs against live slots.
                    # tpulint: disable=TPU020 — deliberate timed
                    # yield, not a predicate wait: this loop IS the
                    # retry loop, and the drain marks the job done
                    # before the wait expires.
                    self._cv.wait(0.002)
                    continue
                self._run_chunk_locked()


# -------------------------------------------------- role entrypoints

def role_telemetry(role: str):
    """(events, tracer) for a replica role from TPUFW_TELEMETRY_DIR —
    per-role files (``events-<role>.jsonl`` / ``trace-<role>.json``)
    so the fleet's artifacts land side by side for trace_merge to
    stitch by trace_id. Null implementations when the dir is unset."""
    tdir = env_opt_str("telemetry_dir")
    if not tdir:
        return obs_events.NULL, obs_trace.NULL
    os.makedirs(tdir, exist_ok=True)
    events = obs_events.EventLog(
        os.path.join(tdir, f"events-{role}.jsonl")
    )
    tracer = obs_trace.Tracer(
        os.path.join(tdir, f"trace-{role}.json"),
        process_name=role, max_events=200_000,
    )
    return events, tracer


def _build_engine(role: str):
    """Construct the engine a replica container runs, from the same
    TPUFW_* contract the monolithic server reads."""
    from tpufw.infer import SamplingConfig
    from tpufw.workloads.serve import build_generator

    model, params, _cfg, restored = build_generator()
    page = env_int("serve_page", 16)
    kv_quant = env_str("serve_kv_quant", "")
    n_slots = max(1, env_int("serve_slots", 8))
    sampling = SamplingConfig(temperature=0.0)
    events, tracer = role_telemetry(role)
    # KV fabric: TPUFW_KV_SPILL pages of host RAM (0 = off) with
    # TPUFW_KV_SPILL_DIR as the overflow + session-store directory;
    # either knob alone enables the tier. The advertisement depth
    # matches the router's TPUFW_ROUTER_PREFIX_AFFINITY so both ends
    # hash the same k chunks.
    spill_pages = max(0, env_int("kv_spill", 0))
    spill_dir = env_str("kv_spill_dir", "")
    spill = None
    if spill_pages or spill_dir:
        from tpufw.infer.spill import SpillTier

        spill = SpillTier(spill_pages, spill_dir)
    common = dict(
        sampling=sampling, page=page, kv_quant=kv_quant,
        n_slots=n_slots, seed_base=env_int("seed", 0),
        prefill_chunk_pages=max(0, env_int("serve_prefill_chunk", 0)),
        spill=spill,
        affinity_k=max(0, env_int("router_prefix_affinity", 0)),
        events=events, tracer=tracer,
    )
    if role == "prefill":
        return PrefillEngine(model, params, **common), restored
    return (
        DecodeEngine(
            model, params,
            chunk=max(1, env_int("serve_chunk", 0)
                      or env_int("stream_chunk", 16)),
            spec_k=env_int("serve_spec_k", 0),
            spec_min_accept=env_float("serve_spec_min_accept", 0.25),
            piggyback=max(0.0, env_float("serve_piggyback", 0.0)),
            **common,
        ),
        restored,
    )


def serve_prefill(engine: PrefillEngine, port: int):
    """Framed-TCP prefill server: JSON request in, bundle out. The
    request's optional ``trace`` field (X-TPUFW-Trace wire form)
    flows into the engine so its stage spans correlate."""

    def handle(frame: bytes) -> bytes:
        # wire: consumes control-frame via req
        req = json.loads(frame.decode("utf-8"))
        if req.get("signals"):
            return json.dumps(engine.signals()).encode()
        prompt = req.get("prompt")
        max_new = req.get("max_new")
        if prompt is None or max_new is None:
            # A signals-shaped (or otherwise field-less) frame must
            # get a structured error reply, not a KeyError traceback
            # laundered through the accept loop.
            return json.dumps(
                {"error": "bad prefill frame: need prompt and max_new"}
            ).encode()
        return engine.prefill(
            [int(t) for t in prompt], int(max_new),
            trace=req.get("trace"), session=req.get("session"),
        )

    srv, bound = transport.serve_frames(port)
    threading.Thread(
        target=transport.accept_loop, args=(srv, handle), daemon=True
    ).start()
    return srv, bound


def serve_decode(engine: DecodeEngine, port: int):
    """Framed-TCP decode server: bundle in, JSON token list out (plus
    the decode-side stage timings — splice_s / first_flush_s /
    n_chunks — the router folds into its TTFT decomposition)."""

    def handle(frame: bytes) -> bytes:
        # wire: consumes control-frame via req
        if frame[:1] == b"{":  # JSON control frame (bundles open TPFB)
            req = json.loads(frame.decode("utf-8"))
            if req.get("signals"):
                return json.dumps(engine.signals()).encode()
            if req.get("drain"):
                # Scale-in hook (manifest 13's preStop + kv_smoke):
                # export live sessions to the store, refuse new work.
                # wire: produces control-frame via drain-reply
                return json.dumps(engine.drain()).encode()
            if req.get("prompt") is not None:
                # Raw-prompt piggyback admission: the router steers
                # here when spare chunk capacity clears the waterline.
                try:
                    slot = engine.submit_raw(
                        [int(t) for t in req["prompt"]],
                        int(req.get("max_new", 1)),
                        trace=req.get("trace"),
                        session=req.get("session"),
                    )
                except (ValueError, RuntimeError) as e:
                    return json.dumps(
                        {"error": f"{type(e).__name__}: {e}"}
                    ).encode()
                out = engine.collect_ex(slot)
                return json.dumps({**out, **engine.signals()}).encode()
            return json.dumps({"error": "expected a page bundle"}).encode()
        try:
            slot = engine.submit(frame)
        except (BundleError, ValueError, RuntimeError) as e:
            return json.dumps(
                {"error": f"{type(e).__name__}: {e}"}
            ).encode()
        out = engine.collect_ex(slot)
        return json.dumps({**out, **engine.signals()}).encode()

    srv, bound = transport.serve_frames(port)
    threading.Thread(
        target=transport.accept_loop, args=(srv, handle), daemon=True
    ).start()
    return srv, bound


def install_drain_handler(engine) -> None:
    """SIGTERM -> drain: kubelet sends TERM at pod deletion/scale-in
    (manifest 13 also hits the peer-port drain op from a preStop hook,
    belt and braces), so live sessions export to the session store,
    then the process lingers TPUFW_SERVE_DRAIN_GRACE_S seconds —
    enough for in-flight collect replies (carrying the ``drained``
    flag) to flush to the router — before exiting."""

    import signal

    def _on_term(signum, frame):
        try:
            engine.drain()
            time.sleep(max(0.0, env_float("serve_drain_grace_s", 5.0)))
        finally:
            raise SystemExit(0)

    signal.signal(signal.SIGTERM, _on_term)


def main_role(role: str) -> int:
    """Container entrypoint for TPUFW_SERVE_ROLE != "". Blocks
    forever (the pod's lifetime IS the replica's lifetime)."""
    if role == "router":
        from tpufw.serve.router import main_router

        return main_router()
    from tpufw.utils.profiling import enable_compile_cache

    enable_compile_cache()
    engine, restored = _build_engine(role)
    port = env_int("serve_peer_port", DEFAULT_PEER_PORT)
    if role == "prefill":
        srv, bound = serve_prefill(engine, port)
    elif role == "decode":
        srv, bound = serve_decode(engine, port)
        install_drain_handler(engine)
    else:
        raise ValueError(
            f"unknown TPUFW_SERVE_ROLE={role!r} "
            "(want prefill|decode|router or empty)"
        )
    print(json.dumps(
        {"serving_role": role, "port": bound, "restored": restored}
    ), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.close()
        engine._tracer.close()
        engine._events.close()
    return 0
