"""The four per-layer metrics that read the slot scheduler's ledger of
passes (``tpufw_serve_pass_seconds_total``, ``..._pass_steps_total``,
``..._pass_starved_seconds_total`` and ``tpufw_serve_ticks_total``):
each reader on a hand-built pair of scrapes gives the value worked by
hand, the two corner cases give 0.0 and None, and scrapes that lack the
series, as every commit before the ledger has them, give None."""

import importlib

import pytest

from benchmarks import harness

SECONDS = 'tpufw_serve_pass_seconds_total{pass="%s"}'
STEPS = 'tpufw_serve_pass_steps_total{pass="%s"}'
STARVED = 'tpufw_serve_pass_starved_seconds_total{pass="%s",phase="%s"}'
TICKS = "tpufw_serve_ticks_total"


def scrape(seconds: dict, steps: dict, starved: dict, ticks: float) -> dict:
    """A parsed ``/metrics`` text: the three families by kind of pass
    (starved also by phase), the series without a label that a labelled
    counter exposes at 0, and the chunk counter."""
    out = {family.split("{")[0]: 0.0 for family in (SECONDS, STEPS, STARVED)}
    out.update({SECONDS % k: v for k, v in seconds.items()})
    out.update({STEPS % k: v for k, v in steps.items()})
    out.update({STARVED % k: v for k, v in starved.items()})
    out[TICKS] = ticks
    return out


#: A 45 s window: 30 s in service. 160 chunks of 8 steps ran with nothing
#: ahead of them in 16.0 s (12.5 ms a step), 40 behind prefill in 9.6 s
#: (30 ms a step), and 4.4 s of passes ran no decode chunk.
BEFORE = scrape(
    {"decode": 100.0, "decode_behind_prefill": 10.0, "prefill_only": 1.0},
    {"decode": 8000.0, "decode_behind_prefill": 800.0, "prefill_only": 0.0},
    {("decode", "serve_fetch"): 2.0, ("decode", "serve_emit"): 1.0,
     ("decode_behind_prefill", "serve_fetch"): 0.5, ("prefill_only", "serve_admit"): 0.25},
    1100.0,
)
AFTER = scrape(
    {"decode": 116.0, "decode_behind_prefill": 19.6, "prefill_only": 5.4},
    {"decode": 9280.0, "decode_behind_prefill": 1120.0, "prefill_only": 0.0},
    # In the window: 0.32 + 0.24 + 0.08 in decode passes, 0.1 + 0.06 in
    # those behind prefill (a phase that was still 0 at the first scrape
    # among them), 0.1 in the passes without a chunk.
    {("decode", "serve_fetch"): 2.32, ("decode", "serve_emit"): 1.24,
     ("decode", "serve_decode_dispatch"): 0.08,
     ("decode_behind_prefill", "serve_fetch"): 0.6,
     ("decode_behind_prefill", "serve_prefill_chunk"): 0.06,
     ("prefill_only", "serve_admit"): 0.35},
    1300.0,
)
OBS = {"prom0": BEFORE, "prom1": AFTER, "seconds": 45.0, "trace": None}

BY_HAND = {
    # (0.64 + 0.16) s starved in decode passes over 200 chunks
    "chunk_boundary_ms": 4.0,
    # 0.9 s starved of 30.0 s in service
    "device_starved_share": 3.0,
    # 25.6 s over 1,600 steps
    "decode_pass_ms_per_step": 16.0,
    # (30.0 - 12.5) ms x 320 of 1,600 steps
    "prefill_stall_ms_per_step": 3.5,
}


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}")


def grown(seconds, steps):
    """OBS with the two scrapes' seconds and steps replaced: the window's
    growth by kind of pass over a first scrape at zero, nothing starved."""
    zero, fed = {k: 0.0 for k in seconds}, {("decode", "serve_fetch"): 0.0}
    return {**OBS, "prom0": scrape(zero, zero, fed, 0.0), "prom1": scrape(seconds, steps, fed, 10.0)}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_the_value_worked_by_hand(name):
    assert reader(name).read(OBS) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_none_where_the_program_lacks_the_series(name):
    """The parent commit's scrape: the chunk counter and the phases, no
    ledger of passes."""
    old = {TICKS: 1300.0, 'tpufw_serve_phase_seconds_total{phase="serve_emit"}': 3.0}
    obs = {"prom0": {**old, TICKS: 1100.0}, "prom1": old, "seconds": 45.0, "trace": None}
    assert reader(name).read(obs) is None
    assert reader(name).read({"prom0": {}, "prom1": {}, "seconds": 45.0, "trace": None}) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_none_where_nothing_was_in_service(name):
    """A window without a request: the series are there and did not grow."""
    assert reader(name).read({**OBS, "prom0": AFTER}) is None


def test_prefill_stall_is_zero_where_no_pass_ran_behind_prefill():
    obs = grown({"decode": 16.0, "decode_behind_prefill": 0.0, "prefill_only": 4.0},
                {"decode": 1280.0, "decode_behind_prefill": 0.0, "prefill_only": 0.0})
    assert reader("prefill_stall_ms_per_step").read(obs) == 0.0
    assert reader("decode_pass_ms_per_step").read(obs) == pytest.approx(12.5)


def test_prefill_stall_is_none_where_no_decode_pass_ran():
    """Every chunk of the window ran behind prefill: nothing to take the
    difference from; the other three still read."""
    obs = grown({"decode": 0.0, "decode_behind_prefill": 9.6, "prefill_only": 0.4},
                {"decode": 0.0, "decode_behind_prefill": 320.0, "prefill_only": 0.0})
    assert reader("prefill_stall_ms_per_step").read(obs) is None
    assert reader("decode_pass_ms_per_step").read(obs) == pytest.approx(30.0)
    assert reader("device_starved_share").read(obs) == 0.0
    assert reader("chunk_boundary_ms").read(obs) == 0.0


def test_the_four_are_in_the_benchmark_and_every_cell_finds_their_readers():
    bench = harness.load_benchmark()
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in BY_HAND}
    assert set(mine) == set(BY_HAND)
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] == "slot_waste_share"}
    assert {m["layer"] for m in mine.values()} == layers
    assert {(m["source"], m["better"], "workloads" in m) for m in mine.values()} == {
        ("program_counter", "lower", False)}
    assert {n: (m["unit"], m["moves"]) for n, m in mine.items()} == {
        "chunk_boundary_ms": ("ms", "tpot_p50_ms"),
        "device_starved_share": ("%", "tokens_per_s_per_chip"),
        "decode_pass_ms_per_step": ("ms", "tpot_p50_ms"),
        "prefill_stall_ms_per_step": ("ms", "tpot_p50_ms"),
    }
    for cell in bench["workloads"]:
        config = harness.load_json(harness.config_entry(bench, cell["config"])["file"])
        assert harness.missing_parts(bench, cell, config) == []
        reported = {m["name"] for m in harness.metrics_of(bench, cell["name"], "per_layer")}
        assert set(BY_HAND) <= reported, cell["name"]


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_missing_parts_names_a_reader_that_is_not_there(name, monkeypatch):
    """``harness.missing_parts`` looks each of the four up by name."""
    import importlib.util

    real = importlib.util.find_spec
    gone = harness.reader_module(name)
    monkeypatch.setattr(importlib.util, "find_spec", lambda mod, *a: None if mod == gone else real(mod, *a))
    bench = harness.load_benchmark()
    cell = bench["workloads"][0]
    config = harness.load_json(harness.config_entry(bench, cell["config"])["file"])
    assert [line for line in harness.missing_parts(bench, cell, config) if name in line]
