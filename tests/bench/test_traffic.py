"""The traffic generator and the arithmetic from records to metrics."""

import statistics

import pytest

from benchmarks import harness, stats, traffic


#: Short chat in bursts: the mix of PERF.md's open question 2, whose file
#: comes with its cell; here it keeps the generator's MMPP branch tested.
CHAT_BURST = {
    "arrivals": {"process": "mmpp", "rate_rps": 1.0, "burst_factor": 4, "dwell_s": 3},
    "prompt": {"base": 32, "alpha": 1.2, "cap": 512, "quantum": 32},
    "output": {"base": 16, "alpha": 1.2, "cap": 256},
    "ramp_s": 6,
}


def mix(name):
    if name == "chat-burst":
        return dict(CHAT_BURST)
    return harness.load_json(harness.traffic_path(name))


@pytest.mark.parametrize("name", ["reason-long", "longprompt-steady", "chat-burst"])
def test_digest_follows_the_seed(name):
    m = mix(name)
    a = traffic.schedule(m, 5, 45, 1000)
    assert traffic.schedule_digest(a) == traffic.schedule_digest(traffic.schedule(m, 5, 45, 1000))
    assert traffic.schedule_digest(a) != traffic.schedule_digest(traffic.schedule(m, 6, 45, 1000))


@pytest.mark.parametrize("name", ["reason-long", "longprompt-steady", "chat-burst"])
def test_every_seed_offers_the_same_work(name):
    """The same arrivals and lengths for every seed, a large one included:
    the seed draws the token ids and nothing else."""
    m = mix(name)
    runs = [traffic.schedule(m, s, 45, 1000) for s in (1, 2, 2**31 + 12345)]
    shapes = [
        (sorted(len(r.prompt) for r in run if r.t >= 0), sorted(r.max_new for r in run if r.t >= 0))
        for run in runs
    ]
    assert shapes[0] == shapes[1] == shapes[2]
    assert [(r.t, len(r.prompt), r.max_new) for r in runs[0]] == [(r.t, len(r.prompt), r.max_new) for r in runs[2]]
    assert [r.prompt for r in runs[0]] != [r.prompt for r in runs[1]]
    other = traffic.schedule(dict(m, shape_seed=1), 1, 45, 1000)
    assert [r.t for r in other] != [r.t for r in runs[0]], "shape_seed reorders the schedule"
    for run in runs:
        assert all(-m["ramp_s"] <= r.t < 45 for r in run)
        assert all(1 <= t < 1000 for r in run for t in r.prompt)


@pytest.mark.parametrize(
    "name,prompt_median,output_median",
    [("reason-long", 256, 256), ("longprompt-steady", 2048, 32 * 2 ** (1 / 1.5)), ("chat-burst", 32 * 2 ** (1 / 1.2), 16 * 2 ** (1 / 1.2))],
)
def test_medians(name, prompt_median, output_median):
    m = mix(name)
    m = dict(m, arrivals=dict(m["arrivals"], rate_rps=40.0))  # a large sample
    run = [r for r in traffic.schedule(m, 3, 45, 1000) if r.t >= 0]
    q = m["prompt"].get("quantum", 1)
    assert abs(statistics.median(len(r.prompt) for r in run) - prompt_median) <= max(0.1 * prompt_median, q / 2)
    assert abs(statistics.median(r.max_new for r in run) - output_median) <= 0.1 * output_median
    assert max(len(r.prompt) for r in run) <= m["prompt"]["cap"]
    assert max(r.max_new for r in run) <= m["output"]["cap"]


def test_reason_sat_means():
    m = mix("reason-long")
    run = [r for r in traffic.schedule(m, 3, 45, 1000) if r.t >= 0]
    assert abs(statistics.mean(len(r.prompt) for r in run) - 394) < 20
    assert abs(statistics.mean(r.max_new for r in run) - 483) < 25


def test_rates():
    for name in ("reason-long", "chat-burst"):
        m = mix(name)
        n = sum(r.t >= 0 for r in traffic.schedule(m, 9, 45, 1000))
        assert abs(n - m["arrivals"]["rate_rps"] * 45) <= 2


def test_mmpp_is_burstier_than_poisson():
    m = mix("chat-burst")
    m = dict(m, arrivals=dict(m["arrivals"], rate_rps=10.0))  # enough arrivals to see it
    times = [r.t for r in traffic.schedule(m, 4, 45, 1000) if r.t >= 0]
    per_second = [sum(1 for t in times if s <= t < s + 1) for s in range(45)]
    flat = dict(m, arrivals={"process": "poisson", "rate_rps": m["arrivals"]["rate_rps"]})
    times = [r.t for r in traffic.schedule(flat, 4, 45, 1000) if r.t >= 0]
    per_second_flat = [sum(1 for t in times if s <= t < s + 1) for s in range(45)]
    assert statistics.pvariance(per_second) > 2 * statistics.pvariance(per_second_flat)


def test_prefixes_and_sessions():
    m = dict(mix("chat-burst"), prefix={"ratio": 0.5, "count": 2, "length": 16},
             sessions={"ratio": 0.3, "turns": 3, "growth": 8})
    run = traffic.schedule(m, 4, 45, 1000)
    heads = {}
    for r in run:
        if len(r.prompt) > 16:
            heads[r.prompt[:16]] = heads.get(r.prompt[:16], 0) + 1
    assert sum(1 for v in heads.values() if v > 5) == 2, "two shared prefixes, each used often"
    prompts = [r.prompt for r in run]
    assert any(len(a) < len(b) and b[: len(a)] == a for a in prompts for b in prompts), "a later turn opens with an earlier prompt"


def rec(due, sent, chunks, status="ok", n_prompt=100, max_new=40):
    return {"due": due, "sent": sent, "chunks": chunks, "status": status, "n_prompt": n_prompt,
            "max_new": max_new, "done": chunks[-1][0] if chunks and status == "ok" else None,
            "tokens": [1] * sum(n for _, n in chunks)}


def test_quartile_spread():
    assert stats.quartile_spread([10, 10, 10, 10, 10, 10]) == 0
    assert stats.quartile_spread([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)


def test_tpot():
    chunks = [(1.0, 1), (1.4, 8), (1.8, 8), (2.2, 8)]
    assert stats.tpot_s(chunks) == pytest.approx(1.2 / 24)
    assert stats.tpot_s(chunks, lo=1.2, hi=2.0) == pytest.approx(0.4 / 8)
    assert stats.tpot_s(chunks, min_tokens=25) is None
    assert stats.tpot_s([(1.0, 5)]) is None


def test_window_stats_counts_failures_as_misses():
    limits = {"tpot_ms": 60.0, "ttft_ms": {"512": 300.0, "2048": 900.0}}
    good = rec(10.0, 10.001, [(10.2, 1), (10.6, 8), (11.0, 8), (11.4, 8)])
    slow_first = rec(11.0, 11.0, [(11.5, 1), (11.9, 8), (12.3, 8), (12.7, 8)])
    long_prompt = rec(12.0, 12.0, [(12.5, 1), (12.9, 8), (13.3, 8), (13.7, 8)], n_prompt=1000)
    slow_tokens = rec(13.0, 13.0, [(13.1, 1), (14.1, 8), (15.1, 8), (16.1, 8)])
    failed = rec(14.0, 14.0, [], status="error")
    never = rec(15.0, 15.0, [], status="cut")
    ramp = rec(5.0, 5.0, [(9.0, 8), (10.5, 16), (11.5, 16)])
    ws = stats.window_stats([good, slow_first, long_prompt, slow_tokens, failed, never, ramp],
                            t0=10.0, seconds=10.0, cutoff=21.0, limits=limits, chips=1)
    assert ws["attempted"] == 6 and ws["failed"] == 2  # the error, and the one with no token by the cutoff
    assert ws["slo_good_share"] == pytest.approx(100.0 * 2 / 6)  # good and long_prompt
    # The ramp's 32 tokens inside the window stay in their own count and leave the rate.
    assert ws["tokens_in_window"] == 4 * 25 + 32
    assert ws["tokens_of_due"] == 4 * 25 and ws["offered_tokens"] == 6 * 40
    assert ws["tokens_per_s_per_chip"] == pytest.approx(10.0)
    assert ws["n_ttft"] == 6 and ws["ttft_max_ms"] == pytest.approx(7000.0)  # the failed one waited to the cutoff
    assert ws["ttft_p50_ms"] == pytest.approx(500.0)  # 100, 200, 500, 500, 6000, 7000
    assert ws["n_tpot"] == 5  # four due in the window and the ramp's, inside the window only
    assert ws["tpot_p50_ms"] == pytest.approx(50.0) and ws["tpot_max_ms"] == pytest.approx(125.0)
    assert stats.ttft_limit_ms(limits["ttft_ms"], 513) == 900.0
    assert ws["backlog_start"] == 2 and ws["backlog_end"] == 2  # the ramp's and the one due at t0; the failed and the unanswered


@pytest.mark.parametrize("case, records, want", [
    # Still streaming when the window closes: counted to there, the chunk after it not, however long the client waits.
    ("cut at the window's close", [rec(12.0, 12.0, [(12.5, 1), (16.0, 8), (19.9, 8), (20.0, 8), (20.9, 8)], status="cut")],
     {"tokens_of_due": 17, "tokens_in_window": 17, "offered_tokens": 40, "attempted": 1, "failed": 0}),
    # An error after some tokens: failed, and what it was sent still counts.
    ("failed with chunks", [rec(12.0, 12.0, [(12.5, 1), (13.0, 8)], status="error")],
     {"tokens_of_due": 9, "tokens_in_window": 9, "offered_tokens": 40, "attempted": 1, "failed": 1}),
    # Only the ramp's request is there: its tokens inside the window are no rate of this window.
    ("no request due", [rec(5.0, 5.0, [(9.0, 8), (10.5, 16), (11.5, 16)])],
     {"tokens_of_due": 0, "tokens_in_window": 32, "offered_tokens": 0, "attempted": 0, "failed": 0}),
    # The server stalls from 13 s past the window's close and then sends the rest: whole answer, half the rate.
    ("a stall inside the window", [rec(12.0, 12.0, [(12.5, 1), (13.0, 19), (20.5, 20)])],
     {"tokens_of_due": 20, "tokens_in_window": 20, "offered_tokens": 40, "attempted": 1, "failed": 0}),
])
def test_window_stats_counts_the_tokens_of_the_requests_due(case, records, want):
    ws = stats.window_stats(records, t0=10.0, seconds=10.0, cutoff=21.0, limits={}, chips=1)
    assert {k: ws[k] for k in want} == want, case
    assert ws["tokens_per_s_per_chip"] == pytest.approx(want["tokens_of_due"] / 10.0)


def replayed(name, tpot_ms, first_s=0.15, chunk=8, stall=(0.0, 0.0)):
    """The mix's own schedule as the records of a server that sends every
    request its first token ``first_s`` after it is due and ``chunk``
    tokens every ``chunk x tpot_ms`` from then on, cut where the client
    stops waiting, through ``window_stats``. ``stall`` = (second of the
    window, seconds): every chunk due from then on comes that much later."""
    m = mix(name)
    t0, seconds = 1000.0, 45.0
    cutoff = t0 + seconds + m["drain_s"]
    records = []
    for r in traffic.schedule(m, 1, seconds, 1000):
        first = t0 + r.t + first_s
        sizes = [1] + [min(chunk, r.max_new - 1 - k) for k in range(0, r.max_new - 1, chunk)]
        chunks = [(first + i * chunk * tpot_ms / 1e3, n) for i, n in enumerate(sizes)]
        chunks = [(t + stall[1] if t >= t0 + stall[0] else t, n) for t, n in chunks]
        chunks = [c for c in chunks if c[0] <= cutoff]
        records.append(rec(t0 + r.t, t0 + r.t, chunks, status="ok" if len(chunks) == len(sizes) else "cut",
                           n_prompt=len(r.prompt), max_new=r.max_new))
    return stats.window_stats(records, t0, seconds, cutoff, {}, 1)


@pytest.mark.parametrize("slower, faster, fell", [
    (27.2, 20.0, 0.0), (20.0, 15.0, 0.0), (15.0, 13.2, 0.0), (13.2, 10.0, 0.0),
    (27.2, 13.2, 0.08),  # PR 42's pair: the driver read -12.9% against a bound of 6%
])
def test_a_faster_server_never_reads_fewer_tokens_a_second(slower, faster, fell):
    """What refused PR 42, kept: ``reason-pool``'s ramp holds answers of
    2,048, 1,536 and 922 tokens, which a server at 27 ms a token streams
    far into the window and one at 13 ms ends before it; the chunks inside
    the window fell with the server's speed, the tokens that the requests
    due in it had been sent when it closed cannot."""
    slow, fast = replayed("reason-pool", slower), replayed("reason-pool", faster)
    assert fast["offered_tokens"] == slow["offered_tokens"] == 11_035
    assert fast["tokens_per_s_per_chip"] > slow["tokens_per_s_per_chip"]
    assert fast["tokens_in_window"] < (1.0 - fell) * slow["tokens_in_window"]
    assert slow["tokens_of_due"] < fast["tokens_of_due"] < fast["offered_tokens"]  # the answer due at 44.9 s has 0.1 s


@pytest.mark.parametrize("name, tpot_ms", [
    ("reason-long", 9.7), ("longprompt-steady", 6.1), ("longdoc-answers", 6.1), ("repo-context", 5.0), ("instruct-burst", 19.7),
    ("reason-pool", 27.2),
])
def test_a_stall_inside_the_window_lowers_the_rate(name, tpot_ms):
    """Tokens and seconds are of one span. At their cells' TPOT (the
    ledger's, PR 41) a server that sends nothing from second 20 to second
    35 of the window reads lower in every mix; one at twice the TPOT
    never reads higher; and no reading passes the offered load."""
    sound, stalled, slow = replayed(name, tpot_ms), replayed(name, tpot_ms, stall=(20.0, 15.0)), replayed(name, 2 * tpot_ms)
    assert 0 < stalled["tokens_of_due"] < sound["tokens_of_due"] <= sound["offered_tokens"]
    assert slow["tokens_of_due"] <= sound["tokens_of_due"]
    assert sound["tokens_per_s_per_chip"] == pytest.approx(sound["tokens_of_due"] / 45.0)


def test_gap_numbers_leave_out_near_tie_routing():
    got = stats.gap_numbers([0.0, 0.5, 0.0, 0.1], [0.3, 0.5, 0.2, 0.1], [0.3, 0.001, 0.2, 0.05], 0.02)
    assert got.pop("logit_noise") > 0  # over all four positions, the near-tie-routed one too
    assert got == {"gap_max": 0.1, "gap_mean": pytest.approx(0.1 / 3), "tokens": 3, "left_out": 1,
                   "moved_share": pytest.approx(1 / 3)}
    assert stats.gap_numbers([0.0, 0.5], [0.3, 0.5], [0.3, 0.001], 0.0)["gap_max"] == 0.5
    with pytest.raises(ValueError):
        stats.gap_numbers([0.1], [0.1], [0.0], 0.02)


def numbers_gaps(ref, best, noise, rng):
    """Gaps of the tokens a program serves whose logits are ``ref`` plus noise."""
    import numpy as np

    ids = np.argmax(ref + noise * rng.normal(size=ref.shape).astype(np.float32), axis=1)
    return (best - ref[np.arange(len(ids)), ids]).tolist()


def test_ids_alone_tell_a_lower_precision_apart():
    """Served tokens are the argmax of (reference logits + the program's
    noise). Weight-only int8 adds about 0.8% to each matmul beside what
    bfloat16 leaves over a residual stream, some twice the logit noise.
    ``logit_noise`` reads that scale back from which near ties flipped,
    within a tenth at a thousand tokens; ``gap_mean`` grows with its
    square but hangs on a few dozen flips; a token altered where it is
    produced lies units below the best and fails both."""
    import numpy as np

    rng = np.random.default_rng(7)
    ref = rng.normal(size=(1200, 4096)).astype(np.float32)
    top = np.sort(ref, axis=1)[:, -2:]
    best, top2 = top[:, 1], (top[:, 1] - top[:, 0]).tolist()
    firm = [1.0] * len(best)

    def numbers(noise):
        return stats.gap_numbers(numbers_gaps(ref, best, noise, rng), top2, firm, 0.0)

    sound = [numbers(0.0125) for _ in range(3)]
    int8 = [numbers(0.025) for _ in range(3)]
    # Noise of 0.0125 on each logit is 0.0177 on a difference of two.
    assert all(abs(n["logit_noise"] / 0.0177 - 1) < 0.25 for n in sound)
    assert min(n["logit_noise"] for n in int8) > 1.5 * max(n["logit_noise"] for n in sound)
    assert min(n["gap_mean"] for n in int8) > 2 * max(n["gap_mean"] for n in sound) > 0
    # A few positions that moved for another reason (an expert taken on a router's near
    # tie), at margins no logit noise of this size reaches, hardly move the reading.
    moved = [g > 0 for g in numbers_gaps(ref, best, 0.0125, rng)]
    plain = stats.logit_noise(top2, moved)
    wide = sorted(range(len(top2)), key=lambda i: -top2[i])[:3]
    flipped = [m or i in wide for i, m in enumerate(moved)]
    assert abs(stats.logit_noise(top2, flipped) / plain - 1) < 0.15
    wrong = [float(b - r[(int(np.argmax(r)) + 1) % 4096]) for b, r in zip(best, ref)]
    broken = stats.gap_numbers(wrong, top2, firm, 0.0)
    assert broken["gap_mean"] > 100 * int8[0]["gap_mean"] and broken["logit_noise"] > 1.0
