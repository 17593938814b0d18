"""The reduction from a trace to busy/idle/per-op numbers on a hand-built
trace, and the FLOP and byte functions against hand counts."""

import json
import os

import pytest

from benchmarks import costs, harness, trace_reduce
from benchmarks.metrics import (
    decode_roofline_share, decode_step_dev_ms, device_idle_share, prefill_dev_ms_per_ktok, prefill_mfu_share,
)


@pytest.fixture(scope="module")
def planes():
    with open(os.path.join(harness.HERE, "data", "tiny_trace.json")) as f:
        raw = json.load(f)
    return {
        p: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
        for p, lines in raw.items() if p != "note"
    }


def test_union_and_gaps():
    assert trace_reduce.union_seconds([(0, 10), (5, 10), (30, 5)]) == pytest.approx(20e-9)
    assert trace_reduce.gaps([(10, 10), (40, 10)], 0, 60) == [(20, 20), (0, 10), (50, 10)]
    assert trace_reduce.module_base("jit__decode_steps_jit(123)") == "jit__decode_steps_jit"


def test_reduce(planes):
    red = trace_reduce.reduce_trace(planes)
    assert red["window_s"] == pytest.approx(12000e-9)
    assert red["busy_s"] == pytest.approx(9500e-9)  # the enclosing while counts as running
    assert red["device_ops"][0] == ("fusion.1", pytest.approx(4000e-9))
    assert not any(name.startswith("while") for name, _ in red["device_ops"])
    assert red["programs"]["jit__decode_steps_jit"]["n"] == 2
    assert red["programs"]["jit__decode_steps_jit"]["step_ms"] == [pytest.approx(0.002)] * 2
    assert red["programs"]["jit__prefill_chunk_jit"]["seconds"] == pytest.approx(2000e-9)
    assert "tokens" not in red["programs"]["jit__prefill_chunk_jit"]  # no hidden size given
    # Longest gaps first, each named by the host event covering most of it.
    assert red["idle_gaps"][0] == ["PjitFunction(_prefill_chunk_jit)", pytest.approx(1000e-9)]
    assert red["idle_gaps"][1] == ["np.asarray", pytest.approx(1000e-9)]
    assert trace_reduce.attribute_gap((20000, 100), []) == "waiting for a request"


def test_gap_goes_to_the_innermost_span_that_accounts_for_it():
    """Spans nest: a gap inside ``serve_prefill_chunk`` > ``serve_row_alloc``
    reads the inner one, whichever the trace lists first, and still does
    where the gap begins a little before the inner span (the device runs
    dry while the outer one is still scanning its queue: what the chip
    showed, 0.72 s of a 0.83 s gap). A span that covers less than half of
    the gap does not account for it."""
    outer, inner = ("serve_prefill_chunk", 1000.0, 9000.0, {}), ("serve_row_alloc", 2000.0, 6000.0, {})
    thread = ("serve_loop", 0.0, 50000.0, {})
    for host in ([outer, inner, thread], [thread, inner, outer]):
        assert trace_reduce.attribute_gap((3000.0, 4000.0), host) == "serve_row_alloc"
        assert trace_reduce.attribute_gap((1500.0, 6000.0), host) == "serve_row_alloc"  # 5500 of 6000
        assert trace_reduce.attribute_gap((3000.0, 6500.0), host) == "serve_row_alloc"  # 5000 of 6500
        assert trace_reduce.attribute_gap((6000.0, 3900.0), host) == "serve_row_alloc"  # 2000 of 3900
        assert trace_reduce.attribute_gap((6000.0, 4000.0), host) == "serve_prefill_chunk"  # 2000 of 4000: not more than half
        assert trace_reduce.attribute_gap((9000.0, 8000.0), host) == "serve_loop"
    # No event covers more than half: the one that covers most, as before.
    assert trace_reduce.attribute_gap((7000.0, 10000.0), [inner, outer]) == "serve_prefill_chunk"
    assert trace_reduce.attribute_gap((60000.0, 100.0), [outer, inner, thread]) == "waiting for a request"


def test_traced_stretch_and_chunk_widths(planes):
    """The stretch is the host clock's between the profiler's start and
    stop where that is longer than first-to-last device operation: idle
    edges count as idle. A prefill execution's tokens are its width, read
    off activation shapes that end in the hidden size and fit a chunk."""
    red = trace_reduce.reduce_trace(planes, 1, 15000e-9, 2048, 256)
    assert red["span_s"] == pytest.approx(12000e-9) and red["window_s"] == pytest.approx(15000e-9)
    assert red["busy_s"] == pytest.approx(9500e-9)
    assert red["idle_gaps"][0][1] == pytest.approx(3000e-9) and "before the first" in red["idle_gaps"][0][0]
    pre = red["programs"]["jit__prefill_chunk_jit"]
    assert pre["tokens"] == 128 and pre["widths_unread"] == 0 and pre["n"] == 1
    ops = planes["/device:TPU:0"]["XLA Ops"]
    # Without the cap a weight-shaped result ties with the activation and wins: the cap is what keeps it out.
    assert trace_reduce.chunk_width(ops, 6000, 2000, 2048, 4096) == 2048
    assert trace_reduce.chunk_width(ops, 1000, 4000, 2048, 256) == 0


@pytest.mark.parametrize("empty", [
    {},
    {"/host:CPU": {"python": [("serve_wait", 0.0, 6e9, {})]}},
    {"/device:TPU:0": {}, "/host:CPU": {"python": [("serve_wait", 0.0, 6e9, {})]}},
    {"/device:TPU:0": {"XLA Ops": [], "XLA Modules": []}},
], ids=["no-plane", "host-only", "device-plane-without-lines", "device-lines-without-events"])
def test_an_empty_stretch_is_a_reading(empty):
    """A server that has nothing in service for the whole traced stretch
    leaves a trace with no device operation: busy 0 s of the stretch, one
    idle gap, no program. The device reads idle, the readers that need a
    program read nothing, and nothing raises."""
    red = trace_reduce.reduce_trace(empty, 1, 6.001, 2048, 256)
    assert red["busy_s"] == 0.0 and red["window_s"] == 6.001 and red["span_s"] == 0.0
    assert red["programs"] == {} and red["device_ops"] == []
    assert red["idle_gaps"] == [["no device operation in the traced stretch", 6.001]]
    obs = {"trace": red, "family": "deepseek_v2", "config": {}, "device": {"kind": "TPU v5 lite"},
           "t0": 0.0, "seconds": 10.0, "traced_from": 2.0, "traced_s": 6.001, "records": [
               {"due": 1.0, "n_prompt": 256, "chunks": [(2.0, 1), (4.0, 16), (8.0, 16)], "done": 8.0}]}
    assert device_idle_share.read(obs) == 100.0
    for reader in (decode_step_dev_ms, decode_roofline_share, prefill_mfu_share, prefill_dev_ms_per_ktok):
        assert reader.read(obs) is None


def test_an_executions_operations_are_found_whatever_the_lines_order(planes):
    """``reduce_trace`` finds the operations inside an execution by
    bisection in the line sorted by start; a line handed over in another
    order reads the same steps, widths, busy time and gaps."""
    turned = {p: {line: evs[::-1] for line, evs in lines.items()} for p, lines in planes.items()}
    a = trace_reduce.reduce_trace(planes, traced_s=20e-6, hidden=2048, widest=512)
    b = trace_reduce.reduce_trace(turned, traced_s=20e-6, hidden=2048, widest=512)
    for key in ("busy_s", "window_s", "span_s", "device_ops"):
        assert a[key] == b[key], key
    assert {k: (v["n"], v.get("tokens"), sorted(v.get("step_ms", []))) for k, v in a["programs"].items()} == {
        k: (v["n"], v.get("tokens"), sorted(v.get("step_ms", []))) for k, v in b["programs"].items()}
    assert sorted(g[1] for g in a["idle_gaps"]) == sorted(g[1] for g in b["idle_gaps"])


def test_loop_steps(planes):
    ops = planes["/device:TPU:0"]["XLA Ops"]
    assert trace_reduce.loop_steps(ops, 1000, 4000) == 2
    assert trace_reduce.loop_steps(ops, 6000, 2000) == 1
    assert trace_reduce.loop_steps(ops, 20000, 10) == 0


def test_readers_on_the_trace(planes):
    red = trace_reduce.reduce_trace(planes, 1, 0.0, 2048, 256)
    keys = harness.model_keys(harness.load_json("benchmarks/configs/deepseek-v2-lite-8l.json"))
    obs = {"trace": red, "family": "deepseek_v2", "config": keys, "device": {"kind": "TPU v5 lite"},
           "t0": 0.0, "seconds": 10.0, "records": [
               {"due": 1.0, "n_prompt": 256, "chunks": [(2.0, 1), (4.0, 16), (8.0, 16)], "done": 8.0},
               {"due": 6.0, "n_prompt": 128, "chunks": [(7.0, 1)], "done": None}]}
    assert device_idle_share.read(obs) == pytest.approx(100 * (1 - 9500 / 12000))
    assert decode_step_dev_ms.read(obs) == pytest.approx(0.002)
    rows, tokens = 1, 256 + 17
    floor_ms = costs.decode_step_bytes("deepseek_v2", keys, rows, tokens) / 819e9 * 1e3
    assert decode_roofline_share.read(obs) == pytest.approx(100 * floor_ms / 0.002)
    # 128 tokens in the trace's one prefill execution, of prompts of 256 and 128.
    need = costs.prefill_chunk_flops("deepseek_v2", keys, 128, [256, 128])
    assert prefill_mfu_share.read(obs) == pytest.approx(100 * need / (2000e-9 * 197e12))
    body = costs.active_matmul_params("deepseek_v2", keys) - keys["vocab_size"] * keys["hidden_size"]
    mean_keys = (256 * 257 / 2 + 128 * 129 / 2) / (256 + 128)
    assert need == pytest.approx(128 * (2 * body + 2 * 16 * (128 + 64 + 128) * 8 * mean_keys))
    for reader in (device_idle_share, decode_step_dev_ms, decode_roofline_share, prefill_mfu_share):
        assert reader.read({**obs, "trace": None}) is None
    # With the stretch's own moments the rows and the prompts are those inside it: at 7.0 + 1.0
    # the second row decodes (its first token came at 7.0) and the first is done; the prompt
    # prefilling in 7.0-9.0 is none, so the window's stand in.
    there = {**obs, "traced_from": 7.0, "traced_s": 2.0}
    floor_ms = costs.decode_step_bytes("deepseek_v2", keys, 1, 128 + 1) / 819e9 * 1e3
    assert decode_roofline_share.read(there) == pytest.approx(100 * floor_ms / 0.002)
    assert prefill_mfu_share.read(there) == pytest.approx(100 * need / (2000e-9 * 197e12))
    only = costs.prefill_chunk_flops("deepseek_v2", keys, 128, [128])
    assert prefill_mfu_share.read({**obs, "traced_from": 5.5, "traced_s": 2.0}) == pytest.approx(
        100 * only / (2000e-9 * 197e12))


def test_deepseek_counts_by_hand():
    c = harness.model_keys(harness.load_json("benchmarks/configs/deepseek-v2-lite-8l.json"))
    p = costs.layer_params("deepseek_v2", c)
    assert p["attn"] == 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048 == 13_762_560
    assert p["dense_ffn"] == 3 * 2048 * 10944 == 67_239_936
    assert p["expert"] == 3 * 2048 * 1408 == 8_650_752
    assert 64 * p["expert"] + p["shared"] + p["router"] == 571_080_704
    assert costs.cache_bytes_per_token("deepseek_v2", c) == (512 + 64) * 2 * 8 == 9216
    active = (13_762_560 + 67_239_936) + 7 * (13_762_560 + 6 * 8_650_752 + 17_301_504 + 131_072) + 209_715_200
    assert costs.active_matmul_params("deepseek_v2", c) == active
    # One decode step of 64 rows reaches nearly every expert: 64 x 6 draws of 64.
    touched = costs.expected_experts_touched(64, 6, 64)
    assert 63.8 < touched < 64
    all_weights = 2 * (81_002_496 + 7 * (13_762_560 + 571_080_704) + 209_715_200)
    got = costs.decode_step_bytes("deepseek_v2", c, 64, 0)
    assert all_weights * 0.995 < got < all_weights * 1.001
    assert costs.decode_step_bytes("deepseek_v2", c, 64, 1000) - got == 1000 * 9216
    one = costs.prefill_flops("deepseek_v2", c, [1])
    assert one == pytest.approx(2 * active + 2 * 16 * (192 + 128) * 8)


def test_mixtral_counts_by_hand():
    c = harness.model_keys(harness.load_json("benchmarks/configs/mixtral-8x7b-3l.json"))
    p = costs.layer_params("mixtral", c)
    assert p["attn"] == 41_943_040 and p["expert"] == 3 * 4096 * 14336 == 176_160_768
    assert 8 * p["expert"] + p["router"] == 1_409_318_912
    assert costs.cache_bytes_per_token("mixtral", c) == 2 * 8 * 128 * 2 * 3 == 12288
    active = 3 * (41_943_040 + 2 * 176_160_768 + 32_768) + 131_072_000
    assert costs.active_matmul_params("mixtral", c) == active
    # 2048 prompt tokens: matmuls plus causal scores and values over 3 layers.
    attn = 2 * 32 * 2 * 128 * 3 * 2048 * 2049 / 2
    assert costs.prefill_flops("mixtral", c, [2048]) == pytest.approx(
        2 * (active - 131_072_000) * 2048 + 2 * 131_072_000 + attn)
    assert costs.expected_experts_touched(8, 2, 16) == pytest.approx(8 * (1 - 0.75**16))
    with pytest.raises(KeyError):
        costs.layer_params("unknown", c)
