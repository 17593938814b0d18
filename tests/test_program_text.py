"""The serving programs of the benchmark's accepted families, as lowered
text at their rehearsal widths, against digests recorded on the commit
before window rings came to ``tpufw/ops/kv_store.py`` (PR 32): a change to
the store, the attention module or the pools for ONE family's sake must
leave the others' decode step and prefill chunk the programs they were
(every static branch a program gains is paid in warm set-up, PERF.md §6,
PR 31).

Where a later change means to alter these programs, record the digests
anew and say so: ``python tests/test_program_text.py > tests/data/program_digests.json``.
The digests hold for the jax version they were recorded under; under
another the test skips until they are recorded again.
"""

import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAMILIES = ("deepseek_v2", "mixtral", "solar_open2")
DIGESTS = os.path.join(ROOT, "tests", "data", "program_digests.json")
PAGE, SLOTS, WIDTH = 16, 2, 32


def program_texts(family: str, platforms=None) -> dict:
    """{"decode", "chunk"}: StableHLO text of the paged pool's two-step
    decode program and of a 32-token prefill chunk through the row twin,
    over abstract bfloat16 parameters; lowered for this backend, or for
    ``platforms`` (("tpu",): jax's TPU rules, without a TPU)."""
    from flax.linen import meta

    from benchmarks import harness
    from tpufw.infer import SamplingConfig, pages, slots

    keys = harness.model_keys(harness.load_json(harness.rehearse_path(family)))
    _, adapter = harness.family_modules(family)
    cls, pc = adapter.program_model(keys, {"moe_dispatch": "sorted"})
    cfg = pc.decode_config()
    row_model = cls(cfg)
    paged = cls(dataclasses.replace(
        cfg, kv_page=PAGE, kv_pages=SLOTS * (cfg.max_seq_len // PAGE) + 1))
    probe = jnp.zeros((1, 8), jnp.int32)
    params = meta.unbox(jax.eval_shape(
        lambda: row_model.init(jax.random.key(0), probe))["params"])
    greedy = SamplingConfig(temperature=0.0)
    abstract = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    cache = abstract(jax.eval_shape(
        lambda p: pages.paged_pool_cache(paged, p, SLOTS), params))
    vec = lambda dtype: jax.ShapeDtypeStruct((SLOTS,), dtype)
    step_keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 2))
    lower = lambda jitted, *a, **k: jitted.trace(*a, **k).lower(
        lowering_platforms=platforms).as_text()
    decode = lower(
        slots._decode_steps_jit,
        paged, params, cache, vec(jnp.int32), vec(jnp.int32), vec(bool),
        vec(jnp.int32), None, step_keys,
        sampling=greedy, pad_id=0, eos_id=None,
    )
    paths, names, leaves, _ = pages._flatten_with_names(cache)
    row_cache = abstract(pages._row_cache_shapes(row_model, params))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype)
    chunk = lower(
        pages._prefill_chunk_jit,
        tuple(leaves), row_cache, params,
        jax.ShapeDtypeStruct((1, WIDTH), jnp.int32),
        jax.ShapeDtypeStruct((WIDTH // PAGE,), jnp.int32),
        scalar(jnp.int32), scalar(jnp.int32), scalar(bool),
        jax.eval_shape(lambda: jax.random.key(0)), None,
        row_model=row_model, sampling=greedy, eos_id=None,
        paths=paths, names=names,
        scale_src=pages.PagedSlotPool._scale_src(paths, names),
        page=PAGE, quant=False,
    )
    return {"decode": decode, "chunk": chunk}


def digests() -> dict:
    return {
        family: {
            name: hashlib.sha256(text.encode()).hexdigest()
            for name, text in program_texts(family).items()
        }
        for family in FAMILIES
    }


@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_without_window_layers_lowers_to_the_program_it_was(family):
    with open(DIGESTS) as f:
        recorded = json.load(f)
    if recorded["jax"] != jax.__version__:
        # Another jax prints another text for the same program: that is
        # no change of the store's. Record anew under the new version.
        pytest.skip(f"digests recorded under jax {recorded['jax']}, this is {jax.__version__}")
    got = {
        name: hashlib.sha256(text.encode()).hexdigest()
        for name, text in program_texts(family).items()
    }
    assert got == recorded["digests"][family], (
        f"{family}'s lowered serving programs changed; if that is meant, "
        "record them anew (this file's docstring)"
    )


def _arena_readers(family: str) -> int:
    """Layers of the family's rehearsal model that read a page pair in a
    decode step: its full-attention layers, or, where later layers attend
    pages an earlier one wrote, the writer and every such reader."""
    from benchmarks import costs, harness

    keys = harness.model_keys(harness.load_json(harness.rehearse_path(family)))
    if "layer_types" in keys:
        return keys["layer_types"].count("full_attention")
    return costs.of(family).readers(keys)


@pytest.mark.parametrize("family", ["olmo_hybrid", "phi4flash"])
def test_on_the_chip_a_kv_pool_decodes_through_one_kernel_a_layer(family, monkeypatch):
    """Lowered for the TPU with the store's kernel rule steered on (here
    the backend is the CPU and the rehearsal's heads are 16 wide), the
    rehearsal pool's decode program calls the Mosaic kernel ONCE a layer
    that reads the arena (Olmo-Hybrid: its full-attention layers;
    Phi-4-mini-flash: the layer that writes the one page pair AND each
    cross layer that reads it with its own queries), no ``lax.switch`` of
    ladder branches and no gather
    of the K/V arena ``[n_pages, page, heads, hd]``: the pages are read
    in place. Its prefill chunk, a row under a scalar cursor, is the
    ladder's program to the letter."""
    import re

    from tpufw.ops import paged_attend

    plain = program_texts(family, ("tpu",))
    monkeypatch.setattr(paged_attend, "serves", lambda *a: True)
    jax.clear_caches()  # the trace above is this one's to jit, else
    try:
        steered = program_texts(family, ("tpu",))
    except Exception as e:  # noqa: BLE001 — whatever this jax raises
        pytest.skip(f"this jax cannot lower a Mosaic kernel off the chip: {e!r}")
    full = _arena_readers(family)
    arena = re.compile(r"stablehlo\.gather.*: \(tensor<\d+x%dx\d+x\d+xbf16>" % PAGE)
    # The kernel is a jitted function of its own, lowered once for the
    # layers' one shape and called from each.
    calls = lambda text: len(re.findall(r"call @paged_attention\w*\(", text))
    assert (calls(plain["decode"]), calls(steered["decode"])) == (0, full)
    assert "tpu_custom_call" in steered["decode"]
    assert "tpu_custom_call" not in plain["decode"]
    assert len(arena.findall(plain["decode"])) == 2 * full
    assert not arena.findall(steered["decode"])
    assert "stablehlo.case" not in steered["decode"]
    assert steered["chunk"] == plain["chunk"]
    jax.clear_caches()  # nor is the steered trace a later test's


def _expert_layers(family: str) -> int:
    """Layers of the family's rehearsal model that run routed experts."""
    from benchmarks import harness

    keys = harness.model_keys(harness.load_json(harness.rehearse_path(family)))
    return keys["num_hidden_layers"] - keys.get("first_k_dense_replace", 0)


@pytest.mark.parametrize("family", FAMILIES)
def test_on_the_chip_a_pool_of_eight_routes_its_live_rows_through_the_kernel(
    family, monkeypatch
):
    """Lowered for the TPU with the expert kernel's rule steered on (here
    the backend is the CPU and the rehearsal's widths are toys), the
    decode program of a pool of EIGHT slots (the shortest with a rung
    under it) calls the Mosaic kernel twice an expert layer (gate and up
    fused, down), in the one branch of a ``case`` whose other branch
    holds the parent's three ``ragged_dot``s; its prefill chunk (a row
    twin of ``t > 1`` under a scalar cursor) is the parent's to the
    letter, and the two-slot pool of the digests above, with no rung
    under it, has no kernel in its decode program either."""
    import re

    from tpufw.ops import moe_live

    monkeypatch.setattr(sys.modules[__name__], "SLOTS", 8)
    plain = program_texts(family, ("tpu",))
    monkeypatch.setattr(moe_live, "serves", lambda *a: True)
    jax.clear_caches()  # the trace above is this one's to jit, else
    try:
        steered = program_texts(family, ("tpu",))
    except Exception as e:  # noqa: BLE001 — whatever this jax raises
        pytest.skip(f"this jax cannot lower a Mosaic kernel off the chip: {e!r}")
    layers = _expert_layers(family)
    calls = lambda text: len(re.findall(r"call @live_experts\w*\(", text))
    ragged = lambda text: text.count('"chlo.ragged_dot"(')
    assert (calls(plain["decode"]), calls(steered["decode"])) == (0, 2 * layers)
    assert "tpu_custom_call" in steered["decode"]
    assert "tpu_custom_call" not in plain["decode"]
    assert ragged(steered["decode"]) == ragged(plain["decode"]) == 3 * layers
    assert steered["decode"].count("stablehlo.case") == (
        plain["decode"].count("stablehlo.case") + layers
    )
    assert steered["chunk"] == plain["chunk"]
    # Two slots have no rung under them: the parent's decode program.
    monkeypatch.setattr(sys.modules[__name__], "SLOTS", 2)
    two = program_texts(family, ("tpu",))
    assert calls(two["decode"]) == 0 and "tpu_custom_call" not in two["decode"]
    jax.clear_caches()  # nor is the steered trace a later test's


if __name__ == "__main__":
    import subprocess

    commit = subprocess.run(
        ["git", "rev-parse", "HEAD"], cwd=ROOT, capture_output=True, text=True
    ).stdout.strip()
    print(json.dumps({"recorded_on": commit, "jax": jax.__version__,
                      "digests": digests()}, indent=1))
