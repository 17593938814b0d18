"""``chained_chunk_share``: the decode chunks the slot scheduler enqueued
before their predecessor was read (``tpufw_serve_chunks_chained_total``)
over the chunks it read (``tpufw_serve_ticks_total``), between a window's
two scrapes. The reader on two hand-made scrapes gives the value worked
by hand, None where no chunk ran, and None on the scrape of a program
without the counter, as the parent commit's is: its result line then
leaves the metric out."""

import pytest

from benchmarks import harness
from benchmarks.metrics import chained_chunk_share

CHAINED = "tpufw_serve_chunks_chained_total"
TICKS = "tpufw_serve_ticks_total"

#: A 45 s window: 800 chunks read, 680 of them enqueued ahead of their
#: predecessor's read (the other 120: an arrival or a prompt's chunks
#: came between, or a request's last chunk).
BEFORE = {CHAINED: 40.0, TICKS: 100.0}
AFTER = {CHAINED: 720.0, TICKS: 900.0}


def obs(before, after):
    return {"prom0": before, "prom1": after, "seconds": 45.0, "trace": None}


def test_reader_gives_the_value_worked_by_hand():
    assert chained_chunk_share.read(obs(BEFORE, AFTER)) == pytest.approx(85.0)


@pytest.mark.parametrize(
    "chained, want", [(0.0, 0.0), (800.0, 100.0)], ids=["plain_order_alone", "every_chunk"]
)
def test_reader_at_both_ends(chained, want):
    after = {CHAINED: BEFORE[CHAINED] + chained, TICKS: AFTER[TICKS]}
    assert chained_chunk_share.read(obs(BEFORE, after)) == pytest.approx(want)


def test_reader_gives_none_where_no_chunk_ran():
    assert chained_chunk_share.read(obs(AFTER, AFTER)) is None


def test_reader_gives_none_where_the_program_lacks_the_counter():
    """The parent commit's scrape: chunks, phases, passes, no such series."""
    old = {TICKS: 900.0, 'tpufw_serve_pass_steps_total{pass="decode"}': 7200.0}
    assert chained_chunk_share.read(obs({**old, TICKS: 100.0}, old)) is None
    assert chained_chunk_share.read(obs({}, {})) is None


def test_it_is_in_the_benchmark_under_the_schedulers_layer_in_every_cell():
    bench = harness.load_benchmark()
    (mine,) = [m for m in bench["per_layer"] if m["name"] == "chained_chunk_share"]
    (boundary,) = [m for m in bench["per_layer"] if m["name"] == "chunk_boundary_ms"]
    assert mine == {**boundary, "name": "chained_chunk_share", "unit": "%", "better": "higher"}
    assert "workloads" not in mine  # every cell that reports tpot_p50_ms
    assert harness.reader_module(mine["name"]) == chained_chunk_share.__name__
    for cell in bench["workloads"]:
        config = harness.load_json(harness.config_entry(bench, cell["config"])["file"])
        assert harness.missing_parts(bench, cell, config) == []
        assert mine in harness.metrics_of(bench, cell["name"], "per_layer"), cell["name"]
