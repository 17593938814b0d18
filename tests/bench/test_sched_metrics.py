"""The four per-layer metrics that read the slot scheduler's own record
(``tpufw_serve_phase_seconds_total`` and the request-chain histograms):
each reader on a hand-built pair of scrapes gives the value worked by
hand, and None on scrapes that lack its series, as every commit before
the counter was added does."""

import importlib

import pytest

from benchmarks import harness

PHASE = 'tpufw_serve_phase_seconds_total{phase="%s"}'


def scrape(phases: dict, hists: dict) -> dict:
    """A parsed ``/metrics`` text: phase seconds, and per histogram the
    cumulative bucket counts by upper edge."""
    out = {PHASE % name: s for name, s in phases.items()}
    for name, buckets in hists.items():
        for le, cum in buckets.items():
            out[f'{name}_bucket{{le="{le}"}}'] = float(cum)
        out[f"{name}_count"] = float(buckets["+Inf"])
    return out


JOIN = "tpufw_serve_join_latency_seconds"
QUEUE = "tpufw_serve_queue_wait_seconds"
PREFILL = "tpufw_serve_prefill_seconds"

#: A 45 s window: the thread waited 10 s for requests, was blocked on the
#: device 25 s, and did 9 s of host work, 6.5 s of it admitting 10 requests.
BEFORE = scrape(
    {"serve_wait": 100.0, "serve_device_wait": 3.0, "serve_admit": 1.0, "serve_row_alloc": 2.0,
     "serve_prefill_chunk": 0.5, "serve_decode_dispatch": 0.25, "serve_decode_chunk": 0.0,
     "serve_emit": 0.25, "serve_prefill": 0.0},
    {JOIN: {"0.25": 0, "0.5": 2, "+Inf": 2},
     QUEUE: {"0.25": 1, "0.3": 2, "0.35": 2, "+Inf": 2},
     PREFILL: {"3": 1, "4": 2, "5": 2, "+Inf": 2}},
)
AFTER = scrape(
    {"serve_wait": 110.0, "serve_device_wait": 28.0, "serve_admit": 1.5, "serve_row_alloc": 8.0,
     "serve_prefill_chunk": 1.5, "serve_decode_dispatch": 0.75, "serve_decode_chunk": 0.25,
     "serve_emit": 1.0, "serve_prefill": 0.0},
    {JOIN: {"0.25": 0, "0.5": 12, "+Inf": 12},
     # In the window: 1 under 0.25, 5 in (0.25, 0.3], 4 in (0.3, 0.35]:
     # the 5th of 10 is the 4th of the 5 in (0.25, 0.3] -> 0.25 + 0.05 * 4/5.
     QUEUE: {"0.25": 2, "0.3": 8, "0.35": 12, "+Inf": 12},
     # In the window: 2 up to 3 s, 4 in (3, 4], 4 in (4, 5]: the 5th of 10 is
     # the 3rd of the 4 in (3, 4] -> 3 + 1 * 3/4.
     PREFILL: {"3": 3, "4": 8, "5": 12, "+Inf": 12}},
)
OBS = {"prom0": BEFORE, "prom1": AFTER, "seconds": 45.0, "trace": None}

BY_HAND = {
    # host = 0.5 + 6.0 + 1.0 + 0.5 + 0.25 + 0.75 = 9.0 s of 45
    "sched_host_share": 20.0,
    # (0.5 + 6.0) s over 10 admissions
    "admit_host_ms": 650.0,
    "queue_wait_p50_ms": 290.0,
    "prefill_span_p50_ms": 3750.0,
}


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}")


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_the_value_worked_by_hand(name):
    assert reader(name).read(OBS) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_none_where_the_program_lacks_the_series(name):
    """The parent commit's scrape: the join histogram and the old
    counters, no phase counter, no chain histograms."""
    old = {k: v for k, v in AFTER.items() if k.startswith(JOIN)}
    old["tpufw_serve_tokens_generated_total"] = 640.0
    obs = {"prom0": dict(old), "prom1": dict(old), "seconds": 45.0, "trace": None}
    assert reader(name).read(obs) is None
    assert reader(name).read({"prom0": {}, "prom1": {}, "seconds": 45.0, "trace": None}) is None


def test_admit_host_ms_is_none_when_nothing_was_admitted():
    obs = {**OBS, "prom1": {**AFTER, JOIN + "_count": BEFORE[JOIN + "_count"]}}
    assert reader("admit_host_ms").read(obs) is None
    assert reader("sched_host_share").read(obs) == pytest.approx(20.0)


def test_the_four_are_in_the_benchmark_under_the_schedulers_layer():
    """Present, in the scheduler's layer, with the source and the
    end-to-end metric each names. Where they stand in the list and which
    cells list them is the reviewing of a PR, not of the suite: later
    PRs append metrics and cells."""
    bench = harness.load_benchmark()
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in BY_HAND}
    assert set(mine) == set(BY_HAND)
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] == "slot_waste_share"}
    assert {m["layer"] for m in mine.values()} == layers
    assert {n: (m["source"], m["moves"]) for n, m in mine.items()} == {
        "sched_host_share": ("program_span", "tokens_per_s_per_chip"),
        "admit_host_ms": ("program_span", "tpot_p50_ms"),
        "queue_wait_p50_ms": ("program_counter", "ttft_p50_ms"),
        "prefill_span_p50_ms": ("program_counter", "ttft_p50_ms"),
    }
