"""Where the traced stretch lies (``runners.serve.trace_offset``, from the
mix's schedule alone) and what is counted inside it (``metrics._steps``:
the rows decoding there, the prompts prefilling there), on the four mixes
as they stand and on hand-made schedules and records."""

import pytest

from benchmarks import harness, traffic
from benchmarks.metrics import _steps
from benchmarks.runners import serve
from benchmarks.traffic import Offered

SECONDS = 45.0
#: mix -> (start of the stretch, the arrival it is anchored to, its prompt
#: tokens), as ISSUE 35 computed them and PERF.md section 4 lists them.
STRETCH = {
    "reason-long": (38.23, 38.48, 1024),
    "longprompt-steady": (16.23, 16.48, 3200),
    "longdoc-answers": (6.21, 6.46, 1088),
    "repo-context": (13.19, 13.44, 15232),
}


def schedule(name, seed=5, seconds=SECONDS):
    return traffic.schedule(harness.load_json(harness.traffic_path(name)), seed, seconds, 1000)


def first_half_tokens(reqs, start):
    return sum(len(r.prompt) for r in reqs if start <= r.t <= start + serve.TRACE_SECONDS / 2)


@pytest.mark.parametrize("name", sorted(STRETCH))
def test_stretch_starts_a_lead_before_an_arrival_and_closes_inside_the_window(name):
    reqs = schedule(name)
    start, anchor = serve.trace_offset(reqs, SECONDS)
    assert anchor in reqs and start == pytest.approx(anchor.t - serve.TRACE_LEAD)
    assert 0.0 <= start and start + serve.TRACE_SECONDS + serve.TRACE_LEAD <= SECONDS
    want = STRETCH[name]
    assert (round(start, 2), round(anchor.t, 2), len(anchor.prompt)) == want


@pytest.mark.parametrize("name", sorted(STRETCH))
def test_stretch_is_the_same_for_every_seed(name):
    """The mix fixes arrivals and lengths, so the seed (a large one too)
    and so the parent and the change are traced over the same stretch."""
    got = [serve.trace_offset(schedule(name, seed), SECONDS) for seed in (1, 2**31 + 12345)]
    assert got[0][0] == got[1][0] and got[0][1].t == got[1][1].t
    assert len(got[0][1].prompt) == len(got[1][1].prompt)


@pytest.mark.parametrize("name", sorted(STRETCH))
def test_stretch_has_the_heaviest_first_half(name):
    reqs = schedule(name)
    start, _ = serve.trace_offset(reqs, SECONDS)
    room = SECONDS - serve.TRACE_SECONDS - serve.TRACE_LEAD
    starts = [r.t - serve.TRACE_LEAD for r in reqs if 0.0 <= r.t - serve.TRACE_LEAD <= room]
    assert len(starts) >= 3, "the mix leaves the choice something to choose from"
    best = max(first_half_tokens(reqs, s) for s in starts)
    assert first_half_tokens(reqs, start) == best
    assert start == min(s for s in starts if first_half_tokens(reqs, s) == best), "the earliest on a tie"


def req(t, n_prompt):
    return Offered(t, (1,) * n_prompt, 8)


def test_a_tie_goes_to_the_earliest_and_a_neighbour_counts_with_its_anchor():
    reqs = [req(-2.0, 900), req(5.0, 100), req(20.0, 100), req(30.0, 60), req(32.5, 60)]
    assert serve.trace_offset(reqs, SECONDS) == (pytest.approx(29.75), reqs[3])  # 60 + 60 in three seconds
    assert serve.trace_offset(reqs[:3], SECONDS) == (pytest.approx(4.75), reqs[1])
    # Due 3.1 s after the anchor: in the stretch, not in its first half.
    assert serve.trace_offset([req(5.0, 100), req(20.0, 60), req(23.35, 60)], SECONDS)[1].t == 5.0


@pytest.mark.parametrize("reqs, seconds, want", [
    ([], 45.0, 19.5),
    ([req(-3.0, 512), req(0.1, 512), req(39.2, 512)], 45.0, 19.5),  # in the ramp, under a lead in, too late to close inside
    ([req(2.0, 512)], 4.0, 0.0),  # a window shorter than the stretch: from its opening
], ids=["empty", "none-fits", "short-window"])
def test_a_schedule_with_no_arrival_to_start_at_is_traced_at_mid_window(reqs, seconds, want):
    assert serve.trace_offset(reqs, seconds) == (pytest.approx(want), None)


def test_an_arrival_fits_up_to_the_edge():
    """8 - 6 - 0.25 s: the latest start that closes a lead inside the window."""
    at_the_edge = req(2.0, 512)
    assert serve.trace_offset([at_the_edge], 8.0) == (pytest.approx(1.75), at_the_edge)
    assert serve.trace_offset([req(2.01, 512)], 8.0) == (pytest.approx(1.0), None)


# ------------------------------------------------ what is counted inside it

#: A 45 s window from 100.0 on the client's clock. Row a decodes from 110
#: to 118; row b from 121 to 125; row c has its first token at 131 and is
#: not done when the records end.
RECORDS = [
    {"due": 109.0, "n_prompt": 256, "chunks": [(110.0, 1), (114.0, 16), (118.0, 16)], "done": 118.0},
    {"due": 120.0, "n_prompt": 64, "chunks": [(121.0, 1), (123.0, 8), (125.0, 8)], "done": 125.0},
    {"due": 126.0, "n_prompt": 128, "chunks": [(131.0, 1)], "done": None},
]
OBS = {"records": RECORDS, "t0": 100.0, "seconds": 45.0}


def traced(start, length=6.0):
    return {**OBS, "traced_from": start, "traced_s": length}


@pytest.mark.parametrize("obs, moment, what, rows", [
    (OBS, 122.5, "mid-window", [64 + 1]),
    ({**OBS, "traced_from": None, "traced_s": None}, 122.5, "mid-window", [64 + 1]),
    (traced(111.0), 114.0, "the middle of the traced stretch", [256 + 17]),
    # No row decodes at 119; the chunks received in 116-122 are a's at 118 and b's at 121,
    # which is b's first: a decodes between 114 and 118, so at 116.
    (traced(116.0), 116.0, "the decode chunk nearest the middle of the traced stretch", [256 + 17]),
    # At 128 nothing decodes; b's chunk at 125 is the only later one of a pair in 125-131.
    (traced(125.0), 124.0, "the decode chunk nearest the middle of the traced stretch", [64 + 9]),
    (traced(130.0), 133.0, "the middle of the traced stretch", [128 + 1]),
    # A stretch in which the client received no token at all.
    (traced(100.0), 103.0, "the middle of the traced stretch", []),
], ids=["untraced", "untraced-keys-none", "row-live-at-the-middle", "nearest-pair-before",
        "nearest-pair-straddles-the-start", "open-row", "empty-stretch"])
def test_live_rows_are_counted_where_the_trace_was_taken(obs, moment, what, rows):
    assert _steps.sample_moment(obs) == (pytest.approx(moment), what)
    assert _steps.live_row_tokens(obs) == rows
    assert _steps.live_rows_and_tokens(obs) == (len(rows), sum(rows))


@pytest.mark.parametrize("obs, lens", [
    (OBS, [256, 64, 128]),  # due in the window
    (traced(111.0), [256, 64, 128]),  # none prefilling in 111-117: the window's
    (traced(119.5), [64]),  # b: due 120, first token 121
    (traced(120.5), [64, 128]),  # b still prefilling at the start, c due at 126
    (traced(126.5), [128]),  # c: due before the stretch, first token inside it
], ids=["untraced", "none-inside", "one", "two", "begun-before"])
def test_prompts_of_the_mfu_are_those_prefilling_in_the_stretch(obs, lens):
    assert _steps.prefilling_prompts(obs) == lens
