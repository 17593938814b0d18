"""``expert_live_step_share``: the decode steps of a pool with routed
experts that ran them over the live rows' assignments alone
(``tpufw_serve_expert_live_steps_total``) over all such steps
(``tpufw_serve_expert_steps_total``), between a window's two scrapes. The
reader on two hand-made scrapes gives the value worked by hand, None
where no such step ran, and None on the scrape of a program without the
counters, as the parent commit's is: its result line then leaves the
metric out."""

import pytest

from benchmarks import harness
from benchmarks.metrics import expert_live_step_share

LIVE = "tpufw_serve_expert_live_steps_total"
STEPS = "tpufw_serve_expert_steps_total"

#: A 45 s window: 4,000 decode steps, 3,600 of them with no more than an
#: eighth of the pool's rows live.
BEFORE = {LIVE: 150.0, STEPS: 200.0}
AFTER = {LIVE: 3750.0, STEPS: 4200.0}
CELLS = [
    "dsv2l-decode-long", "mixtral-prefill-heavy", "solar2-longdoc-answers",
    "laguna-repo-context",
]


def obs(before, after):
    return {"prom0": before, "prom1": after, "seconds": 45.0, "trace": None}


def test_reader_gives_the_value_worked_by_hand():
    assert expert_live_step_share.read(obs(BEFORE, AFTER)) == pytest.approx(90.0)


@pytest.mark.parametrize(
    "live, want", [(0.0, 0.0), (4000.0, 100.0)],
    ids=["a_pool_above_its_eighth_or_off_the_chip", "every_step"],
)
def test_reader_at_both_ends(live, want):
    after = {LIVE: BEFORE[LIVE] + live, STEPS: AFTER[STEPS]}
    assert expert_live_step_share.read(obs(BEFORE, after)) == pytest.approx(want)


def test_reader_gives_none_where_no_expert_step_ran():
    """A model without routed experts exposes both counters at 0."""
    assert expert_live_step_share.read(obs(AFTER, AFTER)) is None
    zero = {LIVE: 0.0, STEPS: 0.0}
    assert expert_live_step_share.read(obs(zero, zero)) is None


def test_reader_gives_none_where_the_program_lacks_the_counters():
    """The parent commit's scrape: chunks, phases, passes, no such series."""
    old = {"tpufw_serve_ticks_total": 900.0, 'tpufw_serve_pass_steps_total{pass="decode"}': 7200.0}
    assert expert_live_step_share.read(obs(old, old)) is None
    assert expert_live_step_share.read(obs({}, {})) is None


def test_it_is_in_the_benchmark_under_the_kernels_layer_in_the_four_moe_cells():
    bench = harness.load_benchmark()
    (mine,) = [m for m in bench["per_layer"] if m["name"] == "expert_live_step_share"]
    assert mine is bench["per_layer"][-1]  # appended, nothing moved
    (roofline,) = [m for m in bench["per_layer"] if m["name"] == "decode_roofline_share"]
    assert (mine["layer"], mine["moves"]) == (roofline["layer"], "tpot_p50_ms")
    assert (mine["unit"], mine["better"], mine["source"]) == ("%", "higher", "program_counter")
    assert mine["workloads"] == CELLS
    assert harness.reader_module(mine["name"]) == expert_live_step_share.__name__
    for cell in bench["workloads"]:
        config = harness.load_json(harness.config_entry(bench, cell["config"])["file"])
        assert harness.missing_parts(bench, cell, config) == []
        listed = mine in harness.metrics_of(bench, cell["name"], "per_layer")
        assert listed == (cell["name"] in CELLS), cell["name"]
