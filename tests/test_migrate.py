"""Page-granular KV migration (tpufw.serve.roles): prefill on one
replica, decode on another, bit-equal to never leaving home.

Contracts, all on CPU with the tiny models:

- PARITY: a request prefilled on replica A, exported as a page
  bundle, and spliced into replica B's arena decodes to EXACTLY the
  greedy tokens of the same request never leaving home — at fp that
  is the one-shot ``generate`` path; at int8 it is an int8 page arena
  that prefills and decodes in place (codes + page-structured scales
  travel raw, so B's storage is bit-identical to A's and the
  dequantize math replays unchanged). Not fp ``generate``: int8 KV is
  a different function of the prompt, and where its top two logits
  sit within the quantization error the ids differ by design.
  The decode arena is pre-polluted so the spliced physical page ids
  differ from the exported ones: the page table hides placement.
- ZERO RETRACES: splicing bundles of varying page counts into a warm
  decode replica re-enters the SAME jitted ``decode_steps`` program.
  Cursors/occupancy/page tables are data; migration adds no shapes.
- EXPORT SNAPSHOT (the `_retire_slot` race): a row finishing
  mid-chunk under arena contention exports the same pages a solo run
  of that prompt exports. The hook reads the chunk-boundary page-
  table snapshot — never the post-retire allocator state, where the
  row's pages may already be re-granted to a queued admission.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.infer import SamplingConfig, generate_text
from tpufw.infer import slots as slots_mod
from tpufw.models import LLAMA_CONFIGS, Llama
from tpufw.serve.bundle import decode_bundle
from tpufw.serve.roles import DecodeEngine, PrefillEngine
from tpufw.serve.transport import LoopbackTransport

GREEDY = SamplingConfig(temperature=0.0)
PAGE = 16
MAX_NEW = 6


@pytest.fixture(scope="module")
def tiny():
    base = LLAMA_CONFIGS["llama3_tiny"].decode_config()
    cfg = dataclasses.replace(base, max_seq_len=64)
    model = Llama(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _engines(model, params, *, kv_quant="", decode_slots=4):
    pe = PrefillEngine(
        model, params, sampling=GREEDY, page=PAGE,
        kv_quant=kv_quant, n_slots=2,
    )
    de = DecodeEngine(
        model, params, sampling=GREEDY, page=PAGE,
        kv_quant=kv_quant, n_slots=decode_slots, chunk=2,
    )
    return pe, de


def _migrate(pe, de, lt, prompt, max_new=MAX_NEW):
    """Prefill on A, ship the bundle over the loopback wire, splice
    into B. Returns B's slot handle."""
    lt.a.send(pe.prefill(prompt, max_new))
    return de.submit(lt.b.recv(timeout=5.0))


@pytest.mark.parametrize("kv_quant", ["", "int8"], ids=["bf16", "int8"])
def test_migration_parity_llama(tiny, kv_quant):
    model, params = tiny
    base = list(range(3, 37))  # 34 tokens = 2 full pages + tail
    prompts = [
        [1, 5, 9],
        [2, 7],
        base,
        base[:PAGE] + [99, 98],  # full-page prefix shared with `base`
    ]
    if kv_quant:
        # Never-left-home at int8: one arena admits (in the same order,
        # so the last prompt is the same prefix hit), prefills and
        # decodes in place.
        from tpufw.workloads.serve import _SlotScheduler

        home = _SlotScheduler(
            model, params, page=PAGE, kv_quant=kv_quant,
            default_sampling=GREEDY,
        )
        want = [home.submit([p], MAX_NEW)[0][0] for p in prompts]
    else:
        want = generate_text(
            model, params, prompts, max_new_tokens=MAX_NEW, sampling=GREEDY
        )
    pe, de = _engines(model, params, kv_quant=kv_quant)
    lt = LoopbackTransport()
    # Pollute the decode arena so spliced physical ids differ from the
    # exported ones — parity must come from the page table, not from
    # landing on the same pages.
    decoy = de.pool.allocator.alloc(1)
    assert decoy is not None
    slots = [_migrate(pe, de, lt, p) for p in prompts]
    got = [de.collect(s) for s in slots]
    assert got == want
    assert pe.migrations == len(prompts) == de.migrations
    # The prefix-sharing prompt attached `base`'s first page from the
    # trie on the PREFILL replica (prefilled once, exported twice).
    assert pe.pool.allocator.in_use > 0  # trie still holds base's pages
    if kv_quant == "int8":
        # Scales ride the wire as fp32 next to the codes.
        state = decode_bundle(pe.prefill(base, MAX_NEW))
        scales = [
            a for p, a in zip(state["paths"], state["arrays"])
            if p.endswith("_scale']")
        ]
        assert scales and all(a.dtype == np.float32 for a in scales)


def test_migration_parity_deepseek_mla(tiny):
    from tpufw.models.deepseek import DEEPSEEK_CONFIGS, Deepseek

    base = DEEPSEEK_CONFIGS["deepseek_tiny"].decode_config()
    cfg = dataclasses.replace(base, max_seq_len=64)
    model = Deepseek(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompts = [[1, 5, 9], [2, 7]]
    max_new = 4
    want = generate_text(
        model, params, prompts, max_new_tokens=max_new, sampling=GREEDY
    )
    pe, de = _engines(model, params, decode_slots=2)
    lt = LoopbackTransport()
    slots = [_migrate(pe, de, lt, p, max_new=max_new) for p in prompts]
    assert [de.collect(s) for s in slots] == want


def test_submit_time_done_job_releases_its_pages(tiny):
    """A bundle that arrives already done (max_new=1: the budget is
    spent by prefill's first sampled token) never passes through a
    decode chunk — so its pages must be released at submit time, not
    leaked until the arena saturates and the replica rejects all
    traffic."""
    model, params = tiny
    pe, de = _engines(model, params)
    lt = LoopbackTransport()
    baseline = de.pool.allocator.in_use
    want = generate_text(
        model, params, [[1, 5, 9]], max_new_tokens=1, sampling=GREEDY
    )
    slot = _migrate(pe, de, lt, [1, 5, 9], max_new=1)
    assert de.pool.allocator.in_use == baseline, (
        "submit-time-done job leaked its arena pages"
    )
    assert de.collect(slot) == want[0]
    assert de.signals()["slots_active"] == 0


def test_migration_adds_zero_decode_retraces(tiny):
    model, params = tiny
    pe, de = _engines(model, params)
    lt = LoopbackTransport()
    # Warm the decode replica: first chunk traces decode_steps once.
    de.collect(_migrate(pe, de, lt, [4, 4, 8]))
    t0 = dict(slots_mod.TRACE_COUNTS)
    # Splices of DIFFERENT page counts (1, 2, and 3 pages), decoded to
    # completion, must re-enter the same program: bundle import writes
    # arena rows + page-table entries, never shapes.
    for prompt in ([5, 6], list(range(2, 20)), list(range(1, 35))):
        de.collect(_migrate(pe, de, lt, prompt))
    assert (
        slots_mod.TRACE_COUNTS["decode_steps"] == t0["decode_steps"]
    ), "migration splices must not retrace decode_steps"


def _export_states(model, params, prompts, *, arena_pages):
    """Run prompts through a `_SlotScheduler` with the page-export
    hook installed; returns {prompt-tuple: exported state}."""
    from tpufw.workloads.serve import _Metrics, _SlotScheduler

    captured = {}

    def hook(job, state):
        captured[tuple(job.prompt)] = state

    sched = _SlotScheduler(
        model, params, eos_id=None, default_sampling=GREEDY,
        seed_base=0, metrics=_Metrics(), page=PAGE,
        arena_pages=arena_pages, page_export=hook,
    )
    outs, _bw = sched.submit(prompts, MAX_NEW, None)
    assert sorted(captured) == sorted(tuple(p) for p in prompts)
    return outs, captured


def test_same_chunk_completion_exports_snapshot_pages(tiny):
    """The satellite regression: under arena contention the third row
    queues until earlier retires free pages, every row finishes
    MID-chunk (budget 5 < chunk k=8), and the freed pages are
    re-granted within the same scheduler pass. Each row's export must
    still be bit-equal to that prompt's export from an UNcontended
    run — an export reading live post-retire state instead of the
    chunk-boundary snapshot sees re-granted or junk-sink pages."""
    model_cfg = LLAMA_CONFIGS["llama3_tiny"].decode_config()
    model = Llama(model_cfg)
    _m, params = tiny
    # 30-token prompts = 3 pages each incl. decode budget; arena of 6
    # usable pages holds only two rows at once.
    prompts = [list(range(10 + i, 40 + i)) for i in range(3)]
    outs, contended = _export_states(
        model, params, prompts, arena_pages=7
    )
    want = generate_text(
        model, params, prompts, max_new_tokens=MAX_NEW, sampling=GREEDY
    )
    assert outs == want
    for p in prompts:
        _solo_outs, solo = _export_states(
            model, params, [p], arena_pages=7
        )
        a, b = contended[tuple(p)], solo[tuple(p)]
        assert a["paths"] == b["paths"]
        assert a["n_pages"] == b["n_pages"] == 3
        # cache_index is replica-local (the slot the row happened to
        # occupy) and is remapped at splice; everything else — the KV
        # bytes above all — must match the solo run exactly.
        for k in ("page", "kv_quant", "token", "pos", "remaining",
                  "done"):
            assert a[k] == b[k], k
        for pa, pb, path in zip(a["arrays"], b["arrays"], a["paths"]):
            assert pa.dtype == pb.dtype and pa.shape == pb.shape
            assert pa.tobytes() == pb.tobytes(), path
        if a["seen"] is not None or b["seen"] is not None:
            assert np.array_equal(a["seen"], b["seen"])
