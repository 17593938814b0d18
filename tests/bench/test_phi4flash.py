"""The phi4flash family in the benchmark: its configuration file against
the catalog's row, the cost functions against the parameter tree and the
store's leaves (tiny, and at the published widths by shapes alone), the
eight readers in a decode step's bytes, the selective scan's own costs, the
mix's draws, the lists the cell is on, the int8 control's table, and the
reader of ``shared_kv_step_share`` on made-up scrapes. What the program
computes against the reference is ``tests/test_phi4flash.py``'s; the
rehearsal of the whole cell is ``tests/bench/test_rehearse.py``'s, which
takes every cell of ``BENCHMARK.json``."""

import dataclasses
import importlib
import json
import os
import re
import statistics

import jax
import jax.numpy as jnp
import pytest

from benchmarks import costs, harness
from benchmarks.costs import phi4flash as cost
from benchmarks.weights import make_weights

FAMILY = "phi4flash"
CELL = "phi4flash-reason-longctx"
NAME = "phi-4-mini-flash-32l"
CONFIG = "benchmarks/configs/phi-4-mini-flash-32l.json"
PAGE = 16


def real_keys():
    return harness.model_keys(harness.load_json(CONFIG))


@pytest.fixture(scope="module")
def built():
    keys = harness.model_keys(harness.load_json(harness.rehearse_path(FAMILY)))
    ref, adapter = harness.family_modules(FAMILY)
    weights = make_weights(ref.weight_specs(keys), 3)
    cls, pc = adapter.program_model(keys, {})
    assert not pc.scan_layers, "the cell serves the unrolled trunk, as the server unrolls any"
    return keys, ref, adapter.to_program(weights, keys), weights, cls, dataclasses.replace(pc, dtype=jnp.float32)


# ------------------------------------------------------- the configuration


def test_catalog_keys_kept_or_listed_as_reduced():
    # The catalog's row as ISSUE 45 drew it, kept beside this file: a test
    # reads nothing outside its checkout.
    with open(os.path.join(os.path.dirname(__file__), "phi4flash_catalog_row.json")) as f:
        row = json.load(f)
    assert row["name"] == "Phi-4-mini-flash-reasoning"
    config = harness.load_json(CONFIG)
    entry = harness.config_entry(harness.load_benchmark(), NAME)
    assert config["source"] == row["source_url"] == entry["source"] and len(entry["source"]) <= 200
    assert list(config["reduced"]) == entry["reduced"] == ["max_position_embeddings"] and len(entry["why"]) <= 200
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value and "->" in config["reduced"][key]
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"], config["max_position_embeddings"]) == (32, 200_064, 16_384)
    assert config["family"] == FAMILY and config["memory"]["weights_bytes_bf16"] == 2 * config["memory"]["parameters"]
    for k in ("mamba", "biases", "norms", "rotary", "layers", "memory_unit", "differential_attention", "parameters", "dtype", "weights"):
        assert len(config["assumed"][k]) > 80, k
    assert "one chip" in config["deployment"] and "WHOLE" in config["deployment"]
    for k in ("logit_noise", "gap_max", "gap_mean", "why"):
        assert config["check"][k]


def test_the_reference_stands_alone_and_covers_every_answer():
    ref, _ = harness.family_modules(FAMILY)
    with open(ref.__file__) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+tpufw", src, re.M), "the reference imports nothing of the program"
    bench = harness.load_benchmark()
    cell = harness.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reason-longctx", 1) and len(cell["why"]) <= 200
    config = harness.load_json(CONFIG)
    mix = harness.load_json(harness.traffic_path(cell["traffic"]))
    assert mix["output"]["cap"] == ref.MAX_AT and mix["rehearse"]["output"]["cap"] <= ref.MAX_AT
    assert mix["prompt"]["cap"] + mix["output"]["cap"] == config["max_position_embeddings"]
    assert config["vocab_size"] % ref.HEAD_BLOCKS == 0 and config["max_position_embeddings"] % ref.ROW_BLOCK == 0
    assert (ref.D_STATE, ref.D_CONV, ref.EXPAND) == (cost.D_STATE, cost.D_CONV, cost.EXPAND) == (16, 4, 2)
    assert harness.missing_parts(bench, cell, config) == []


def test_the_blocked_mlp_and_head_give_what_one_product_gives(built, monkeypatch):
    keys, ref, _, weights, _, _ = built
    monkeypatch.setattr(ref, "ROW_BLOCK", 8)
    monkeypatch.setattr(ref, "HEAD_BLOCKS", 4)
    x = jax.random.normal(jax.random.key(0), (32, keys["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda x: ref.mlp(weights, "layers.0.mlp.", keys, x))(x)
        monkeypatch.setattr(ref, "ROW_BLOCK", 64)
        want = ref.mlp(weights, "layers.0.mlp.", keys, x)
        head = jax.jit(ref.head)(x, weights["embed"])
        whole = x @ weights["embed"].astype(jnp.float32).T
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert head.shape == (32, keys["vocab_size"]) and float(jnp.max(jnp.abs(head - whole))) < 1e-5


# --------------------------------------------------------------- the costs


def test_cost_functions_count_the_parameter_tree_and_the_stores_leaves(built):
    """At the rehearsal widths, against what the program really holds."""
    from tpufw.infer import SamplingConfig, pages
    from tpufw.ops import kv_store

    keys, _, params, _, cls, pc32 = built
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert cost.parameters(keys) == n == pc32.n_params()
    cfg = dataclasses.replace(pc32.decode_config(), max_seq_len=256)
    paged = dataclasses.replace(cfg, kv_page=PAGE, kv_pages=2 * (256 // PAGE) + 1)
    pool = pages.PagedSlotPool.create_paged(
        cls(paged), cls(cfg), params, 2, sampling=SamplingConfig(temperature=0.0), eos_id=None, prefix_cache=True)
    state = ring = page_bytes = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(pool.cache):
        role, name = kv_store.path_role(path), kv_store.leaf_name(path)
        if role.kind == kv_store.STATE:
            state += leaf.nbytes // 2
        elif name in ("ring_key", "ring_value"):
            ring += leaf.nbytes // 2
        elif role.kind == kv_store.PAGE:
            page_bytes += leaf.nbytes // (leaf.shape[0] * leaf.shape[1])
    assert cost.state_bytes_per_row(keys, bytes_per=4) == state, "float32 activations here"
    assert cost.ring_bytes_per_row(keys, bytes_per=4) == ring
    # The cost function counts the model's 4 heads of 8 (2 pairs of 16); a
    # page holds a whole tile of 8 stored pairs (``kv_store_heads``, derived).
    assert (pc32.kv_store_heads, pc32.kv_store_head_dim, pc32.kv_pairs) == (8, 16, 2)
    assert cost.cache_bytes_per_token(keys, bytes_per=4) * 8 // 2 == page_bytes
    assert cost.readers(keys) == pool.page_readers == pc32.kv_page_readers == 2


def test_the_published_widths_by_shapes_alone():
    """The model at its published widths, never built: ``eval_shape`` of
    its init against the cost functions and the configuration's file."""
    from flax.linen import meta

    config = harness.load_json(CONFIG)
    c = harness.model_keys(config)
    _, adapter = harness.family_modules(FAMILY)
    cls, pc = adapter.program_model(c, config["assumed"])
    shapes = meta.unbox(jax.eval_shape(lambda: cls(pc).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == cost.parameters(c) == pc.n_params() == config["memory"]["parameters"] == 3_852_562_944
    assert (pc.kv_store_heads, pc.kv_store_head_dim, pc.kv_page_readers, pc.mamba_dt_rank) == (16, 128, 8, 160)
    assert shapes["full"]["attn"]["o"]["kernel"].shape == (20, 128, 2560) and "k" not in shapes["cross_layer_6"]["cross"]["attn"]
    assert shapes["memory"]["mamba"]["A_log"].shape == (16, 5120)


def test_cost_goldens():
    """ISSUE 45's reckoning, redone by the cost functions."""
    c = real_keys()
    p = cost.layer_params(c)
    assert [cost.layer_total(c, k) for k in ("mamba", "window", "gmu", "cross")] == [119_895_040, 98_322_304, 104_867_840, 91_766_144]
    assert cost.layer_total(c, "memory") == cost.layer_total(c, "mamba") and cost.layer_total(c, "full") == cost.layer_total(c, "window")
    assert p["embed"] == 512_163_840 and cost.parameters(c) - p["embed"] == 3_340_399_104
    kinds = cost.layer_kinds(c)
    assert [kinds.count(k) for k in cost.KINDS] == [8, 8, 1, 1, 7, 7]
    memory = harness.load_json(CONFIG)["memory"]
    assert cost.cache_bytes_per_token(c) == costs.cache_bytes_per_token(FAMILY, c) == 5_120 == memory["cache_bytes_per_token"]
    assert cost.state_bytes_per_row(c) == 9 * (327_680 + 30_720) == memory["state_bytes_per_slot"]
    assert cost.ring_bytes_per_row(c) == 8 * 512 * 5_120 == memory["ring_bytes_per_slot"]
    weights = 2 * cost.parameters(c)
    assert costs.decode_step_bytes(FAMILY, c, 0, []) == weights
    a_row = 2 * 2560 + 2 * cost.state_bytes_per_row(c)
    assert costs.decode_step_bytes(FAMILY, c, 2, [100, 9800]) == weights + 2 * a_row + 8 * 9900 * 5_120 + 8 * (100 + 512) * 5_120
    head = p["embed"]
    whole = costs.prefill_flops(FAMILY, c, [1024])
    # Query-key pairs: the whole row in the full layer and the seven cross
    # layers, a window of 512 in the eight window layers.
    whole_rows, windowed = 8 * 1024 * 1025 / 2, 8 * (512 * 513 / 2 + 512 * 512.0)
    assert whole == pytest.approx(2.0 * (cost.active_matmul_params(c) - head) * 1024 + cost.mamba_chunk_flops(c, 1024) + 2.0 * head
                                  + 40 * 2.0 * 192 * (whole_rows + windowed))
    assert costs.prefill_chunk_flops(FAMILY, c, 1024, [1024]) == pytest.approx(whole - 2.0 * head)


def test_a_decode_step_counts_eight_readers_of_one_arena():
    c = real_keys()
    one, long = cost.decode_step_bytes(c, [1000]), cost.decode_step_bytes(c, [3000])
    assert long - one == 8 * 2000 * 5_120, "the one page pair, read by the layer that writes it and seven more"
    assert cost.arena_read_bytes(c, [9800] * 10) == 8 * 98_000 * 5_120
    # ISSUE 45's regime: ten rows of 9.8k make the readers a third of the step's bytes.
    share = cost.arena_read_bytes(c, [9800] * 10) / cost.decode_step_bytes(c, [9800] * 10)
    assert 0.30 < share < 0.36
    none = cost.decode_step_bytes(c, [])
    assert none == 2 * 3_852_562_944 and cost.decode_step_bytes(c, [0]) - none == 2 * 2560 + 2 * 3_225_600


def test_the_selective_scans_own_costs():
    c = real_keys()
    assert cost.mamba_dims(c) == (5120, 16, 4, 160)
    assert cost.mamba_chunk_flops(c, 512) == 6.0 * 5120 * 16 * 512 * 9
    assert cost.mamba_chunk_flops(c, 1024) == 2 * cost.mamba_chunk_flops(c, 512)
    stream = 5120 * 2 + 2 * 5120 * 4 + 2 * 16 * 2
    assert cost.mamba_chunk_bytes(c, 512) == 9 * (2 * 5120 * 16 * 4 + 512 * stream)
    assert cost.mamba_step_bytes(c, 32) == 32 * 9 * (2 * 5120 * 16 * 4 + stream)


# ----------------------------------------------------------------- the mix


def test_the_mix_is_issue_45s():
    from benchmarks import traffic

    mix = harness.load_json(harness.traffic_path("reason-longctx"))
    assert mix["prompt"] == {**mix["prompt"], "base": 4096, "alpha": 1.0, "cap": 14336, "quantum": 64}
    assert mix["output"] == {**mix["output"], "base": 768, "alpha": 1.0, "cap": 2048}
    arr = mix["arrivals"]
    assert arr["process"] == "poisson" and (mix["ramp_s"], mix["drain_s"], mix["shape_seed"]) == (40, 45, 0)
    assert mix["server_env"] == {"TPUFW_SERVE_SLOTS": 32, "TPUFW_SERVE_PAGE": 16, "TPUFW_SERVE_PREFILL_CHUNK": 32,
                                 "TPUFW_SERVE_CHUNK": 8, "TPUFW_SERVE_CACHE_FLOOR": 16384}
    # 0.8 x the knee, rounded down to a whole number of requests a window.
    assert arr["rate_rps"] == pytest.approx(int(0.8 * mix["knee"]["knee_rps"] * 45 + 1e-9) / 45.0, abs=6e-4)
    reqs = traffic.schedule(mix, 1, 45.0, 200_064)
    in_win = [r for r in reqs if r.t >= 0]
    assert len(in_win) == round(arr["rate_rps"] * 45)
    lens = sorted(len(r.prompt) for r in in_win)
    assert all(n % 64 == 0 for n in lens) and lens[0] >= 4096 and lens[-1] == 14336
    assert 7680 <= statistics.median(lens) <= 8704, "a median of about 8192"
    assert 0.2 <= sum(n == 14336 for n in lens) / len(lens) <= 0.36, "about two in seven at the cap"
    outs = sorted(r.max_new for r in in_win)
    assert outs[0] >= 768 and outs[-1] == 2048 and 1300 <= outs[len(outs) // 2] <= 1800
    assert 0.3 <= sum(n == 2048 for n in outs) / len(outs) <= 0.45, "about three in eight at the cap"
    assert all(len(r.prompt) + r.max_new <= 16384 for r in reqs)
    limits = mix["limits"]
    assert limits["tpot_ms"] > 0 and max(int(k) for k in limits["ttft_ms"]) >= 14336


def test_the_cell_is_on_the_lists_issue_45_names():
    per_layer = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    # ``in``, not a position or a whole list: the next cell is appended to
    # these lists by a PR that may not edit this file.
    for name in ("attended_keys_share", "state_hbm_share", "state_live_share", "window_hbm_share", "window_keys_share",
                 "first_token_p50_ms", "slo_good_share.tpot", "ttft_max_ms.tpot", "gen_late_max_ms.tokens",
                 "join_wait_p50_ms.tpot", "queue_wait_p50_ms.tpot", "prefill_span_p50_ms.tpot", "shared_kv_step_share"):
        assert CELL in per_layer[name]["workloads"], name
    new = per_layer["shared_kv_step_share"]
    assert (new["source"], new["unit"], new["better"], new["moves"]) == ("program_counter", "%", "higher", "tpot_p50_ms")
    assert new["layer"] == per_layer["attended_keys_share"]["layer"]
    e2e = {m["name"]: m for m in harness.load_benchmark()["end_to_end"]}
    assert CELL not in e2e["ttft_p50_ms"]["workloads"], "arrivals wait on decode chunks: first_token_p50_ms is the per-layer reading"


# ------------------------------------------------------------ the controls


def test_the_programs_int8_path_knows_every_projection(built):
    """``--control int8_weights``: every kernel a ``projection`` declares
    is in the quantizer's table (one it left in bfloat16 would fail the
    quantized module's init), and nothing else is touched."""
    from tpufw.ops.quant import quantize_params

    keys, _, params, _, cls, pc32 = built
    q = quantize_params(params)
    mamba_, attn = q["memory"]["mamba"], q["full"]["attn"]
    for name in ("in_proj", "x_proj", "dt_proj", "out_proj"):
        assert {"q_kernel", "scale"} <= set(mamba_[name]), name
    assert "bias" in mamba_["dt_proj"] and mamba_["A_log"].dtype == jnp.float32 and mamba_["conv"].dtype == jnp.bfloat16
    for name in ("q", "k", "v", "o"):
        assert set(attn[name]) == {"q_kernel", "scale", "bias"}, name
    cross = q["cross_layer_0"]
    assert set(cross["gmu"]["gmu"]["in_proj"]) == {"q_kernel", "scale"} and set(cross["cross"]["attn"]) >= {"q", "o", "subln"}
    assert set(q["embed"]) == {"embedding"} and set(q["final_norm"]) == {"scale", "bias"}
    tokens = jax.random.randint(jax.random.key(4), (24,), 1, keys["vocab_size"])
    out = cls(dataclasses.replace(pc32, quantized_weights=True)).apply({"params": q}, tokens[None])[0]
    assert out.shape == (24, keys["vocab_size"]) and bool(jnp.all(jnp.isfinite(out)))


def test_the_state_fault_script_knows_the_family():
    import sys

    sys.path.insert(0, os.path.join(harness.ROOT, "scripts"))
    try:
        import shared_reader_fault
        import solar_state_fault
    finally:
        sys.path.pop(0)
    module, rule, at, dtype = solar_state_fault.STATEFUL[FAMILY]
    model = importlib.import_module(module)
    assert callable(getattr(model, rule)) and getattr(model, dtype) == jnp.float32
    import inspect

    assert list(inspect.signature(getattr(model, rule)).parameters)[at] == "state"
    assert shared_reader_fault.FAULTS == ("one_short",)


# ------------------------------------------------- shared_kv_step_share


def scrapes(readers_chunk=7 * 4096.0, with_counter=True):
    """Two made-up scrapes: 1,000 decode steps in 125 chunks of 8 at ten
    live rows of 9,800 tokens, and one prefill chunk that read 4,096 key
    slots."""
    per_step = 10 * 9800
    before = {
        "tpufw_serve_attended_key_slots_total": 5.0, "tpufw_serve_ticks_total": 3.0, "tpufw_serve_tick_rows_total": 9.0,
        'tpufw_serve_pass_steps_total{pass="decode"}': 7.0, 'tpufw_serve_pass_steps_total{pass="decode_behind_prefill"}': 1.0,
        'tpufw_serve_shared_key_slots_total{call="decode"}': 35.0, 'tpufw_serve_shared_key_slots_total{call="chunk"}': 0.0,
    }
    grown = {
        "tpufw_serve_attended_key_slots_total": 1000 * per_step + 4096.0, "tpufw_serve_ticks_total": 125.0,
        "tpufw_serve_tick_rows_total": 1250.0,
        'tpufw_serve_pass_steps_total{pass="decode"}': 900.0, 'tpufw_serve_pass_steps_total{pass="decode_behind_prefill"}': 100.0,
        'tpufw_serve_shared_key_slots_total{call="decode"}': 7.0 * 1000 * per_step,
        'tpufw_serve_shared_key_slots_total{call="chunk"}': readers_chunk,
    }
    after = {k: before[k] + v for k, v in grown.items()}
    if not with_counter:
        before = {k: v for k, v in before.items() if "shared" not in k}
        after = {k: v for k, v in after.items() if "shared" not in k}
    return {"prom0": before, "prom1": after, "family": FAMILY, "config": real_keys()}


def test_shared_kv_step_share_prices_the_counters_by_the_cost_functions():
    reader = importlib.import_module(harness.reader_module("shared_kv_step_share"))
    c = real_keys()
    want = 100.0 * cost.arena_read_bytes(c, [9800] * 10) / (
        cost.arena_read_bytes(c, [9800] * 10) + cost.decode_step_bytes(c, [0] * 10))
    assert reader.read(scrapes()) == pytest.approx(want) and 30.0 < want < 36.0
    # A program without the counter (the parent), a family with one reader
    # and a window with no decode step report nothing, and do not raise.
    assert reader.read(scrapes(with_counter=False)) is None
    assert reader.read({**scrapes(), "family": "olmo_hybrid", "config": harness.model_keys(
        harness.load_json("benchmarks/configs/olmo-hybrid-7b-16l.json"))}) is None
    idle = scrapes()
    idle["prom1"] = dict(idle["prom0"])
    assert reader.read(idle) is None
